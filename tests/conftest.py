import os

# Keep tests on the single real CPU device (the 512-device override is
# strictly for launch/dryrun.py, which sets it before its own jax import).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# No persistent compile cache in tests, nor in the entry points they start
# as subprocesses (which inherit this environment).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
