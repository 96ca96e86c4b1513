"""The limit readings of program and control, at a reduced size on the
CPU."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import limits  # noqa: E402
from test_onchip_cell import BENCH, CELLS, base  # noqa: E402,F401

KW = dict(require_tpu=False, use_cache=False)


def test_limit_readings_of_program_and_control(base):  # noqa: F811
    rows = list(limits.readings(BENCH, CELLS[1], 1.0, [4, 5],
                                control={5}, **KW, base=base))
    assert [r["seed"] for r in rows] == [4, 5]
    assert "control" not in rows[0]
    for r in rows:
        assert r["program"]["correct"] and r["failed"] == 0
        assert r["program"]["checks"]["served_len_mismatch"]["value"] == 0
    ctl = rows[1]["control"]
    assert not ctl["correct"]
    assert ctl["tokens"] == rows[1]["program"]["tokens"]
    assert ctl["mean_gap"] > 10 * max(rows[1]["program"]["mean_gap"], 1e-4)
