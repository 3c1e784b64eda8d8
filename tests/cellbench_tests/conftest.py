"""Makes ``roots`` (the builder of temporary data roots) importable from the
tests of this directory whatever pytest's import mode."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
