"""The control of the rollout comparison, at a size a test run holds:
the reference network with int8 matmul operands, put in the program's
place, must read as not correct under the configuration's limit while
the program reads as correct: through a whole run of the harness
(``bench/run.py --control int8``, as on the chip at the cell's own
size) and in the readings the limits are set from
(``bench/tools/readings.py``)."""
import json
import sys

import pytest

import helpers

sys.path.insert(0, str(helpers.BENCH / "tools"))
import readings  # noqa: E402


# At these sizes a run compares about a hundred decisions, so the int8
# control flips a choice on some seeds only; seeds 1-4 hold one for each
# cell.
@pytest.mark.parametrize("workload,seeds", [
    ("rollout.tiny-mlp.grid", ["1", "2", "3", "4"]),
    ("rollout.tiny-attn.backlog", ["1", "2", "3", "4"])])
def test_int8_control_fails_where_the_program_passes(tmp_path, capsys,
                                                         workload, seeds):
    root = helpers.tiny_root(tmp_path)
    readings.main(["--workload", workload, "--seeds", *seeds,
                   "--cpu", "--root", str(root)])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith('{"seed"')]
    limit = helpers.TINY_LIMITS["widest_score_gap"]
    assert len(lines) == 4
    assert all(x["program"]["widest"] <= limit for x in lines)
    assert all(m == 0 for x in lines for m in x["mismatched"])
    assert max(x["int8"]["widest"] for x in lines) > limit


# At these sizes a run compares about a hundred decisions; on these
# seeds the int8 control puts a different action first at some row.
@pytest.mark.parametrize("workload,seed", [("rollout.tiny-mlp.grid", 2),
                                           ("rollout.tiny-attn.backlog", 1)])
def test_int8_control_in_the_programs_place_is_not_correct(tmp_path, workload,
                                                            seed):
    root = helpers.tiny_root(tmp_path)
    line = helpers.run_cell(root, workload, seed=seed, seconds=1.0)
    assert line["correct"], line["checks"]
    line = helpers.run_cell(root, workload, seed=seed, seconds=1.0,
                            control="int8")
    assert not line["correct"]
    gap = line["checks"]["widest_score_gap"]
    assert gap["value"] > gap["limit"]
    assert line["checks"]["decisions_mismatched"]["value"] == 0
