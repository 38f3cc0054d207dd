"""Analytic model FLOPs of a federated MoCo v3 round on a ViT.

The benchmark's own copy of the program's per-sample accounting
(``repro.roofline.client_costs.flops_per_sample_round``, without depth
dropout): dense multiply-adds counted as 2 FLOPs, backward = 2x the
forward of the trainable part, nothing for remat recompute. Server calibration is a
step over the whole current sub-model with no alignment.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class VitCosts:
    tokens: int
    patch_in: int
    d: int
    d_ff: int
    proj_hidden: int
    proj_dim: int
    pred_hidden: int

    @classmethod
    def from_config(cls, cfg):
        m, s = cfg["model"], cfg["ssl"]
        n = (cfg["image_size"] // cfg["patch_size"]) ** 2
        return cls(tokens=n + 1, patch_in=cfg["patch_size"] ** 2 * 3,
                   d=m["d_model"], d_ff=m["d_ff"],
                   proj_hidden=s["proj_hidden"], proj_dim=s["proj_dim"],
                   pred_hidden=s["pred_hidden"])

    @property
    def stem(self):
        return 2 * self.tokens * self.patch_in * self.d

    @property
    def block(self):
        t, d = self.tokens, self.d
        attn = 2 * t * d * (3 * d) + 2 * t * t * d * 2 + 2 * t * d * d
        return attn + 2 * t * d * self.d_ff * 2

    @property
    def proj(self):
        return 2 * (self.d * self.proj_hidden
                    + self.proj_hidden * self.proj_hidden
                    + self.proj_hidden * self.proj_dim)

    @property
    def pred(self):
        return 2 * (self.proj_dim * self.pred_hidden
                    + self.pred_hidden * self.proj_dim)


def step_flops(c: VitCosts, *, sub: int, active_from: int,
               align: bool) -> float:
    """FLOPs of one sample (both views) in one local step. With a frozen
    prefix (``active_from > 0``) the online weights' prefix runs once per
    view and the target and alignment branches start from it, so each
    costs its ``sub - active_from`` blocks only."""
    act = active_from
    fwd_frozen = c.stem + act * c.block
    fwd_active = (sub - act) * c.block + c.proj + c.pred
    online = 2 * (fwd_frozen + fwd_active)
    branch = (sub - act) * c.block if act > 0 else c.stem + sub * c.block
    target = 2 * (branch + c.proj)
    bwd = 2 * 2 * fwd_active
    total = online + target + bwd
    if align:
        total += 2 * branch
    return float(total)


def round_flops(cfg, traffic) -> dict:
    """Per round: client samples, client FLOPs, calibration samples and
    calibration FLOPs (the stage plan of the traffic's window rounds)."""
    c = VitCosts.from_config(cfg)
    L = cfg["model"]["num_layers"]
    if traffic["schedule"] == "e2e":
        sub, act, align, calib = L, 0, False, False
    elif traffic["schedule"] == "lw_fedssl":
        sub, act, align, calib = traffic["stage"], traffic["stage"] - 1, \
            True, True
    else:
        raise ValueError(traffic["schedule"])
    bs = traffic["batch"]
    steps = traffic["local_epochs"] * (traffic["pool"] // traffic["clients"]
                                       // bs)
    client_samples = traffic["cohort"] * steps * bs
    out = {"client_samples": client_samples,
           "client_flops": client_samples * step_flops(
               c, sub=sub, active_from=act, align=align),
           "calib_samples": 0, "calib_flops": 0.0}
    if calib:
        n = traffic["aux"] // min(bs, traffic["aux"]) * min(bs, traffic["aux"])
        out["calib_samples"] = traffic["server_epochs"] * n
        out["calib_flops"] = out["calib_samples"] * step_flops(
            c, sub=sub, active_from=0, align=False)
    return out
