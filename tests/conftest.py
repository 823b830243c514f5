import os

# Tests must see exactly ONE device (the dry-run sets its own 512-device
# flag inside launch/dryrun.py, never globally).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from hypothesis import settings  # noqa: E402

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")
