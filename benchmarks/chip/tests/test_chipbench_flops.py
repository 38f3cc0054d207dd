"""The analytic FLOP counts pinned at the three cells' plans, and equal
to the program's own accounting."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import flops  # noqa: E402


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def deit_s():
    """ViT-Tiny's file at DeiT-S widths (d 384, MLP 1536)."""
    c = cfg("vit-tiny")
    c["model"].update(d_model=384, num_heads=6, d_ff=1536)
    return c


@pytest.mark.parametrize("widths,sub,active_from,align,gflop", [
    ("vit-tiny", 12, 0, False, 6.160),     # FedMoCo e2e
    ("vit-tiny", 12, 11, True, 2.269),     # LW-FedSSL stage 12
    ("deit-s", 12, 0, False, 23.05),       # FedMoCo e2e at DeiT-S widths
])
def test_step_flops_per_sample(widths, sub, active_from, align, gflop):
    c = flops.VitCosts.from_config(
        deit_s() if widths == "deit-s" else cfg(widths))
    got = flops.step_flops(c, sub=sub, active_from=active_from,
                           align=align) / 1e9
    assert got == pytest.approx(gflop, abs=5e-3)


def test_round_flops_of_the_lw_cell():
    traffic = json.loads((BENCH / "traffic" / "lw_fedssl.s12.cohort8.json")
                         .read_text())
    f = flops.round_flops(cfg("vit-tiny"), traffic)
    steps = 3 * (5000 // 1024)
    assert f["client_samples"] == traffic["cohort"] * steps * 1024
    assert f["calib_samples"] == 3 * 4 * 1024
    c = flops.VitCosts.from_config(cfg("vit-tiny"))
    assert f["calib_flops"] == f["calib_samples"] * flops.step_flops(
        c, sub=12, active_from=0, align=False)


@pytest.mark.parametrize("sub,active_from,align", [
    (12, 11, True),                        # LW-FedSSL stage 12
    (12, 0, False),                        # FedMoCo e2e
])
def test_step_flops_is_the_programs_count(sub, active_from, align):
    from repro.core.schedule import RoundPlan
    from repro.roofline import client_costs
    plan = RoundPlan(round_idx=0, stage=sub, sub_layers=sub,
                     active_from=active_from, new_stage=False,
                     download_stages=(0, sub),
                     upload_stages=(active_from, sub),
                     server_calibrate=align, align=align, depth_dropout=0.0)
    want = client_costs.flops_per_sample_round(client_costs.vit_costs(),
                                               plan)
    got = flops.step_flops(flops.VitCosts.from_config(cfg("vit-tiny")),
                           sub=sub, active_from=active_from, align=align)
    assert got == want


def test_round_flops_of_the_e2e_cell():
    traffic = json.loads((BENCH / "traffic" / "e2e.cohort3.json")
                         .read_text())
    f = flops.round_flops(cfg("vit-small"), traffic)
    assert f["client_samples"] == 3 * 3 * (5000 // 1024) * 1024
    assert f["client_flops"] / f["client_samples"] / 1e9 == pytest.approx(
        23.05, abs=5e-3)
    assert f["calib_samples"] == 0 and f["calib_flops"] == 0.0
