"""Operation and byte counts the benchmark's per-layer metrics divide by.

Each count comes from the configuration's and the traffic's sizes, never
from the program: ``vgg9`` and ``lora_transformer`` give the useful
FLOPs of a round's local training; ``kernels`` gives, per round, the
FLOPs and the logical, unpadded bytes of each Pallas kernel the round
calls.
"""
