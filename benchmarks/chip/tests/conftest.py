import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import pytest  # noqa: E402

import common  # noqa: E402

# The recorded sample predates the decode kernel that the program runs
# now: its decode calls hold the split-K kernel the program ran then.
# Renamed, it stands for the kernel that the decode reader reads by name.
OLD_DECODE_KERNEL = "paged_attention_splitk"


@pytest.fixture
def recorded_trace():
    """``trace_sample.json`` with its decode kernel under today's name."""
    compact = common.load_json(common.HERE / "tests" / "trace_sample.json")
    for dev in compact["devices"].values():
        for op in dev["ops"]:
            if op[3] and op[0].startswith(OLD_DECODE_KERNEL + "."):
                op[0] = "paged_decode_attention" + op[0][len(
                    OLD_DECODE_KERNEL):]
    return compact
