"""The chip benchmark's own CPU tests, collected here so that the suite
runs them: its arithmetic and lookup (``chipbench/tests/test_yardstick.py``)
and the reduction of the program's names in a trace
(``chipbench/tests/test_program_trace.py``).  The planted-fault tests of
``correct`` (``chipbench/tests/test_correct.py``) take minutes on a CPU
and run on their own: ``python -m pytest chipbench/tests``."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent / "chipbench"
for p in (BENCH, BENCH / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from test_program_trace import *  # noqa: E402,F401,F403
from test_yardstick import *  # noqa: E402,F401,F403
