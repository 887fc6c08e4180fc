import os
import sys

# Tests run single-device (the dry-run pins 512 host devices in its own
# process; never here).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
