"""The benchmark's own tests run on the CPU:
python -m pytest benchmarks/tests -q -p no:cacheprovider
(they are not part of the repo's tier-1 suite, which collects tests/)."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# Several virtual devices, as the repo's own tests have them: with one,
# XLA:CPU fails the second InferenceSession of a process with "Function
# transpose_copy_fusion not found".
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)
