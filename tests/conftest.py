# NOTE: do NOT set XLA_FLAGS / device-count overrides here — the smoke tests
# must see the real single CPU device.  Distribution tests that
# need multiple devices spawn subprocesses with their own XLA_FLAGS.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
