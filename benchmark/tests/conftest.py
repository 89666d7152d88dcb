"""The benchmark's own tests run apart from the repo's tier-1 suite:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

They need the repo root on the path (``benchmark`` and ``bigdl_tpu`` are
imported from there) and four virtual CPU devices for the mesh cell."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
