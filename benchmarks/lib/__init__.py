"""The yardstick: what decides a number, kept where later PRs cannot edit it.

peaks    the chip's published peak rates, keyed by exact device_kind
stats    medians, quartile spread, segment rates, tails with failures
flops    operations and bytes of a step, computed from shapes
traffic  the generators that turn a traffic file and a seed into work
xplane   the reduction from a profiler trace to busy time, gaps and ops
spans    the benchmark's own host spans around its calls into the program
memory   device memory at its fullest sampled instant
"""
