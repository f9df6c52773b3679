"""The benchmark's yardstick: traffic generation, the serving loop and its
timestamps, the reduction from profiler traces to device times, and the
result line.  Nothing here imports the program under test; configuration
modules under ``bench/configs`` do, and only to build the served system."""
