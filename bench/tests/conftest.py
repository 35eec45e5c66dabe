import os

# the benchmark's CPU tests run on the host CPU at tiny sizes; these
# settings match tests/conftest.py and leave any caller's own in place
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_backend_optimization_level=0")
