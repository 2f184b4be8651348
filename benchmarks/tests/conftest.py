"""The benchmark's own tests run on the CPU at the files' ``rehearse``
sizes: ``python -m pytest benchmarks/tests -q`` from the repo's root (by
hand; tier-1 is ``tests/``). The ResNet50 ones compile the zoo's network
for the CPU, minutes the first time."""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
