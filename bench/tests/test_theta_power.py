"""The power-capped Theta configuration (``theta-power``, MRSch Sec. V-E)
and its ``capped`` traffic: the capacities and widths the program runs,
the keys ``test_resources`` sized and pinned, and S6-S10 refused where
the configuration has no power."""
import json

import pytest

import helpers
from harness import core, refnet, workload
from test_resources import program_state_dim, theta_power

CONFIGS = helpers.BENCH / "configs"
THETA_POWER = json.loads((CONFIGS / "theta-power.json").read_text())
CAPPED = json.loads((helpers.BENCH / "traffic" / "capped.json").read_text())
# Prose that names the deployment; every other value is theta_power()'s.
PROSE = {"source", "deployment", "assumed"}


def test_capacities_are_thetas_with_a_500_kw_budget():
    assert workload.capacities(THETA_POWER) == [4392, 1293, 500]


def test_state_width_is_the_program_agents():
    assert refnet.state_dim(THETA_POWER) == program_state_dim(THETA_POWER) \
        == THETA_POWER["state_dim"] == 12420


def test_keys_are_those_test_resources_sized():
    sized = theta_power()
    assert set(THETA_POWER) == set(sized)
    for key in set(sized) - PROSE:
        assert THETA_POWER[key] == sized[key], key
    assert set(THETA_POWER["assumed"]) == set(sized["assumed"]) | {"power"}


def test_the_cell_finds_its_files():
    cell = core.find_cell(helpers.REPO, "rollout.theta-power.capped")
    assert cell.config == THETA_POWER and cell.traffic == CAPPED
    assert cell.chips == 1 and "widest_score_gap" in cell.limits


def test_capped_names_the_power_scenarios():
    assert CAPPED["trace"]["scenarios"] == ["S6", "S7", "S8", "S9", "S10"]
    assert CAPPED["trace"]["compression"] == 2.0


def test_capped_is_refused_without_power():
    theta_mlp = json.loads((CONFIGS / "theta-mlp.json").read_text())
    with pytest.raises(ValueError, match="no power resource"):
        workload.make_traces(theta_mlp, CAPPED["trace"], CAPPED["work_seed"],
                             1, 8)
