import os

# keep smoke tests on 1 device; the dry-run (and ONLY the dry-run) forces 512
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
