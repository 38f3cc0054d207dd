"""Model FLOP utilisation of the whole step: the analytic FLOPs of the
window's client local steps and server calibration steps
(``chipbench.flops``; remat recompute not counted) over the window's
seconds times the device's bf16 peak (``peaks.json``), in percent."""


def read(ctx):
    if ctx.peak is None or not ctx.rounds:
        return None
    f = ctx.round_flops
    work = ctx.rounds * (f["client_flops"] + f["calib_flops"])
    return 100.0 * work / (ctx.window_s * ctx.peak["bf16_flops_per_s"]
                           * ctx.cell["chips"])
