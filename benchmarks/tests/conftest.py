"""CPU only: these tests never look for a chip."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
