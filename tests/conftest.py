import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the benchmark's harness (``bench``), whose trace reduction some tests use
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

# tests keep JAX's persistent compilation cache off, in this process and in
# every subprocess a test starts (the entry points enable it otherwise)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
