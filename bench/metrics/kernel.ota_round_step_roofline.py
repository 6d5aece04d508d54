"""kernel.ota_round_step_roofline: percent of the HBM roofline that the
``ota_round_step`` Pallas kernel reaches: (bytes the traced cell-rounds
need / HBM bandwidth) / the summed device time of the kernel's events,
over all chips.  Bytes per cell-round: the [N, D] uplink at its wire
width plus the [D] noise and the [D] params read and written (unpadded
D); the kernel is memory-bound at every wire width."""
from bench import peaks, xtrace

KERNEL = "ota_round_step"


def read(ctx):
    if not ctx.planes or not ctx.peaks or not ctx.sweeps:
        return None
    ns = sum(xtrace.kernel_ns(ctx.ops[p], KERNEL)[0] for p in ctx.planes)
    if ns <= 0:
        return None
    cfg = ctx.cell.config
    need = (ctx.sweeps * ctx.cells * ctx.rounds
            * peaks.round_step_bytes(cfg["num_devices"], cfg["param_dim"],
                                     ctx.cell.traffic["uplink"]))
    return 100.0 * (need / ctx.peaks["hbm_bw"]) / (ns / 1e9)
