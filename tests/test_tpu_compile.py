"""Compile the main-path kernels for a described TPU v5e, without a chip.

Interpret mode accepts block layouts the TPU compiler refuses (a (1, 1)
block of a (B, 1) array, a (1, bq) row block of a (B, H, Sq) array) and
VMEM working sets over the limit.  Each test here lowers one kernel-backed
program with ``interpret=False`` against a ``v5e:2x2`` topology described
on the CPU host and asserts the compiled HLO holds a Mosaic kernel
(``tpu_custom_call``).  Nothing runs: these tests say the chip's compiler
takes the program, not that it computes the right numbers (the
interpret-mode parity tests and ``chip_smoke.py`` do that).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.darknet_ref import DARKNET19_CFG, YOLOV3_CFG
from repro.core import make_engine
from repro.core.darknet.network import Network
from repro.kernels import ops, sharded

# qwen2-0.5b attention widths (configs/qwen2_0p5b.py).
_H, _KV, _HD = 14, 2, 64


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off (a
    described-chip compile can be written but never read back without a
    chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *specs):
    """Compile; assert a Mosaic kernel is there.  Returns the HLO text."""
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_gemm_forward_compiles(one_chip):
    # Darknet-19's 3x3/256 conv at 28x28, batch 4, as its im2col GEMM.
    m, k, n = 3136, 1152, 256

    def fwd(x, w, scale, shift):
        return ops.matmul(x, w, scale, shift, act="leaky", interpret=False)

    _assert_kernel(fwd, _spec(one_chip, (m, k)), _spec(one_chip, (k, n)),
                   _spec(one_chip, (n,)), _spec(one_chip, (n,)))


def test_gemm_backward_compiles(one_chip):
    m, k, n = 3136, 1152, 256

    def loss(x, w, scale, shift):
        return ops.matmul(x, w, scale, shift, act="leaky",
                          interpret=False).sum()

    _assert_kernel(jax.grad(loss, argnums=(0, 1, 2, 3)),
                   _spec(one_chip, (m, k)), _spec(one_chip, (k, n)),
                   _spec(one_chip, (n,)), _spec(one_chip, (n,)))


def test_flash_forward_batched_kv_len_compiles(one_chip):
    b, s = 4, 1024

    def fwd(q, k, v, kv_len):
        return ops.attention(q, k, v, kv_len, causal=True, interpret=False)

    _assert_kernel(fwd, _spec(one_chip, (b, s, _H, _HD)),
                   _spec(one_chip, (b, s, _KV, _HD)),
                   _spec(one_chip, (b, s, _KV, _HD)),
                   _spec(one_chip, (b,), jnp.int32))


@pytest.mark.parametrize("with_kv_len", [False, True])
def test_flash_forward_backward_compiles(one_chip, with_kv_len):
    b, s = 2, 1024

    def loss(q, k, v, kv_len):
        o = ops.attention(q, k, v, kv_len if with_kv_len else None,
                          causal=True, interpret=False)
        return o.sum()

    _assert_kernel(jax.grad(loss, argnums=(0, 1, 2)),
                   _spec(one_chip, (b, s, _H, _HD)),
                   _spec(one_chip, (b, s, _KV, _HD)),
                   _spec(one_chip, (b, s, _KV, _HD)),
                   _spec(one_chip, (b,), jnp.int32))


def test_split_kv_decode_compiles(one_chip):
    b, skv = 4, 2048

    def decode(q, k, v, kv_len):
        return ops.attention_decode(q, k, v, kv_len, causal=True,
                                    interpret=False)

    _assert_kernel(decode, _spec(one_chip, (b, 1, _H, _HD)),
                   _spec(one_chip, (b, skv, _KV, _HD)),
                   _spec(one_chip, (b, skv, _KV, _HD)),
                   _spec(one_chip, (b,), jnp.int32))


def test_stride2_conv_compiles_without_gather(one_chip):
    """YOLOv3's 52x52x256 -> 26x26x512 downsample: im2col reads each tap
    from one of the input's four phases with unit stride; strided slices
    on the tiled W axis were lowered to gathers, one per tap."""
    eng = make_engine("pallas", "fp32_strict", interpret=False)

    def conv(x, w):
        return eng.conv2d(x, w, size=3, stride=2, pad=1, act="leaky")

    text = _assert_kernel(conv, _spec(one_chip, (1, 52, 52, 256)),
                          _spec(one_chip, (9 * 256, 512)))
    assert " gather(" not in text


def _entry_names(text):
    """Name stems of the entry computation's instructions."""
    entry = text[text.index("\nENTRY"):]
    return [re.sub(r"(\.\d+)+$", "", m.group(1))
            for m in re.finditer(r"^\s*(?:ROOT )?%([\w\-.]+) = ", entry,
                                 re.M)]


def _kernel_stems(text):
    """Name stems of the Mosaic kernels, which the device trace files
    their time under."""
    return [re.sub(r"(\.\d+)+$", "", m.group(1))
            for m in re.finditer(r"%([\w\-.]+) = \S+ custom-call\(.*"
                                 r"custom_call_target=\"tpu_custom_call\"",
                                 text)]


def _im2col_copies(text):
    """The entry's concatenations, as XLA lowers them: `concatenate` and
    the fusions of dynamic-update-slices."""
    return sum(n == "concatenate" or "dynamic-update-slice" in n
               for n in _entry_names(text))


# Entry instructions that write no array of their own: the kernels'
# custom-calls (their outputs are the GEMM's), views and async starts.
_NOT_WRITTEN = {"parameter", "bitcast", "get-tuple-element", "tuple",
                "constant", "custom-call", "copy-start", "slice-start"}


def _entry_bytes_written(text):
    """Logical bytes of the arrays the entry computation's instructions
    outside the kernels write: im2col's matrices (whatever fusion XLA
    files them under), pads, pooling, copies; every array here is
    float32."""
    entry = text[text.index("\nENTRY"):]
    total = 0
    for m in re.finditer(r"^\s*(?:ROOT )?%[\w\-.]+ = \w+\[([\d,]*)\]\{[^}]*\}"
                         r" ([\w\-]+)\(", entry, re.M):
        dims, op = m.groups()
        if op not in _NOT_WRITTEN:
            total += 4 * int(np.prod([int(d) for d in dims.split(",") if d]))
    return total


def _compile_network(one_chip, cfg, batch, hw):
    net = Network(cfg, make_engine("pallas", "fp32_strict", interpret=False))
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          jax.eval_shape(net.init, jax.random.key(0)))
    return _assert_kernel(net.apply, params,
                          _spec(one_chip, (batch, hw, hw, 3)))


def test_darknet19_network_compiles(one_chip):
    """Darknet-19 at bucket 8: every convolution's kernel is named
    `matmul`, the eight implicit-patch ones included, and only the im2col
    of the stem and the three 7x7 convolutions (at most one copy per tap)
    is left to concatenate."""
    text = _compile_network(one_chip, DARKNET19_CFG, 8, 224)
    assert _kernel_stems(text) == ["matmul"] * 19
    assert _im2col_copies(text) <= 9 * 4
    # 884 MB per step (with im2col for every 3x3 conv: 2,022 MB).
    assert _entry_bytes_written(text) <= 1_000e6


def test_yolov3_network_compiles(one_chip):
    """YOLOv3-416 at bucket 1: GEMM extents Darknet-19 never had (K = 27 at
    M = 173056, N = 255 heads, K = 768 and 384 after the cross-scale
    routes, M = 676 with no aligned row divisor, whose (676, 768, 256)
    plan once overflowed Mosaic's scoped VMEM), five stride-2 downsamples
    whose im2col holds no gather, and 32 implicit-patch convolutions from
    208 down to 13 wide.  Every kernel is named `matmul`; what concatenates
    is the im2col of the six convolutions that keep it (the stem, the
    downsamples; at most one copy per tap) and the four routes."""
    text = _compile_network(one_chip, YOLOV3_CFG, 1, 416)
    assert " gather(" not in text
    assert _kernel_stems(text) == ["matmul"] * 75
    assert _im2col_copies(text) <= 9 * 6 + 4
    # 875 MB per image (with im2col for every 3x3 conv: 3,212 MB).
    assert _entry_bytes_written(text) <= 1_000e6


def test_implicit_conv_backward_compiles(one_chip):
    """The implicit-patch convolution at YOLOv3's 52-wide Cin 128 and its
    custom VJP (the im2col formulation's backward)."""
    eng = make_engine("pallas", "fp32_strict", interpret=False)

    def loss(x, w, scale, shift):
        return eng.conv2d(x, w, scale=scale, shift=shift, size=3, pad=1,
                          act="leaky").sum()

    text = _assert_kernel(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                          _spec(one_chip, (1, 52, 52, 128)),
                          _spec(one_chip, (9 * 128, 256)),
                          _spec(one_chip, (256,)), _spec(one_chip, (256,)))
    assert "matmul" in _kernel_stems(text)


# (rows, query length, key length): rows 64 shard over the 4-chip data
# axis and 63 do not; a 64-token prefill chunk of one sequence divides
# nothing; a one-token decode of one sequence splits the key axis.
@pytest.mark.parametrize("rows, sq, skv", [(64, 64, 576), (63, 64, 576),
                                           (64, 1, 576)])
def test_sharded_kernels_compile_on_four_chip_mesh(four_chips, rows, sq,
                                                   skv):
    """Every kernel the sharded backend dispatches under a multi-chip mesh
    sits inside shard_map — the SPMD partitioner refuses a bare Mosaic
    kernel even when nothing is sharded."""
    rep = NamedSharding(four_chips, P())

    def step(x, w, q, k, v, kv_len):
        y = sharded.matmul(x, w, act="silu", interpret=False)
        o = sharded.attention(q, k, v, kv_len, causal=True, interpret=False)
        return y, o

    with four_chips:
        _assert_kernel(step, _spec(rep, (rows, 896)), _spec(rep, (896, 128)),
                       _spec(rep, (1, sq, _H, _HD)),
                       _spec(rep, (1, skv, _KV, _HD)),
                       _spec(rep, (1, skv, _KV, _HD)),
                       _spec(rep, (1,), jnp.int32))
