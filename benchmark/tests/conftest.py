"""The benchmark's own tests run on the CPU: ``pytest benchmark/tests``.
They check the yardstick (reducer, copies, reference, files) and walk
every driver in rehearsal; no time taken here is a device number. A
variable that is set is left as it is: collected together with other
tests, this file changes nothing for them (the rehearsals give their
children a compile cache of their own, ``test_rehearsal.cache_dir``)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
