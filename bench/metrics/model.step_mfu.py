"""model.step_mfu: percent of the chips' bf16 peak that the model FLOPs of
the traced window's completed cell-rounds make up.

FLOPs per cell-round = devices x samples per device per round x the
configuration's ``flops_per_sample`` (forward plus backward, from its
layer shapes; eval left out).  The samples per device per round come
from the configuration module's ``samples_per_device(cfg, batch_size)``
where it defines one, else from the shard of ``samples_per_class`` x
``num_classes`` over the devices, cut to the minibatch.  The bf16 peak
is the yardstick because the chip runs the program's f32 matmuls at
default precision as single bf16 passes."""


def read(ctx):
    if not ctx.window_s or not ctx.peaks or not ctx.sweeps:
        return None
    cfg, batch = ctx.cell.config, ctx.cell.traffic["batch_size"]
    count = getattr(ctx.cell.model, "samples_per_device", None)
    if count is not None:
        per_device = count(cfg, batch)
    else:
        x = (cfg["samples_per_class"] * cfg["model"]["num_classes"]
             // cfg["num_devices"])
        per_device = batch if 0 < batch < x else x
    flops = (ctx.sweeps * ctx.cells * ctx.rounds * cfg["num_devices"]
             * per_device * ctx.cell.model.flops_per_sample(cfg))
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
