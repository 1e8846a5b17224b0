import os
import sys

# tests run against the source tree; single CPU device (the dry-run and
# the distributed tests manage their own device counts via subprocesses)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
