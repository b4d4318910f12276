"""The benchmark's own tests: CPU only, run by hand
(``python -m pytest benchmark/tests -q``); not part of the repo's tier-1 run."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent))
