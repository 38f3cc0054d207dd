"""CPU rehearsals of whole benchmark runs at a tiny size: the harness,
the program's timed path and the plain reference, with the chip check
skipped. Sound runs come out correct; the control and each fault a
training cell can have come out not correct against the cell's limits."""
import jax
import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import compare, faults, harness, reference

LW = "vit-tiny.lw_fedssl.s12"
# (cell, schedule in place of the traffic's own): the LW cell with its
# own plan and with FedMoCo's, and the e2e cell
CASES = [pytest.param((LW, None), id="lw_fedssl"),
         pytest.param((LW, "e2e"), id="e2e"),
         pytest.param(("vit-small.e2e", None), id="vit-small.e2e")]


@pytest.mark.parametrize("case", CASES)
def test_a_tiny_run_is_correct_and_reports_its_metrics(case):
    result = tiny.run_tiny(case[0], schedule=case[1])
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "client_samples_per_s", "peak_hbm_bytes", "setup_s"}
    assert result["metrics"]["client_samples_per_s"]["value"] > 0
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"


def test_the_reference_builds_the_programs_initial_weights():
    from repro.core import ssl as ssl_mod
    from repro.configs.base import SSLConfig
    _, _, cfg, traffic, _ = tiny.tiny_cell(LW)
    model, ssl, train, _ = harness.program_configs(
        harness.load_family(cfg), cfg, traffic)
    key = jax.random.PRNGKey(3)
    prog = ssl_mod.ssl_init(key, ssl_mod.make_vit_encoder(model), ssl)
    ref = reference.init_state(key, cfg)
    a, b = compare.leaf_paths(prog), compare.leaf_paths(ref)
    assert set(a) == set(b)
    for p in a:
        np.testing.assert_array_equal(np.asarray(a[p]), np.asarray(b[p]),
                                      err_msg=p)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", faults.FAULTS)
def test_a_planted_fault_is_not_correct(kind, case):
    with faults.planted(kind):
        result = tiny.run_tiny(case[0], seed=777, schedule=case[1])
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("kind", faults.LATER_ROUND_FAULTS)
def test_a_fault_of_later_rounds_is_not_correct(kind):
    with faults.planted(kind):
        result = tiny.run_tiny(LW, seed=778)
    checks = result["checks"]
    assert all(c["value"] <= c["limit"] for n, c in checks.items()
               if n.endswith(".r0")), checks
    assert not result["correct"], checks


@pytest.mark.parametrize("case", CASES)
def test_the_control_is_not_correct(case):
    cell, schedule = case
    run = harness.Run(cell, 4242, require_tpu=False,
                      cell_files=lambda *_: tiny.tiny_cell(cell, schedule))
    ref = run.reference()
    _, online, losses, _ = run.reference("control")
    ok, checks = compare.judge(run.numbers(ref, online, losses),
                               run.limits["limits"])
    assert not ok, checks
