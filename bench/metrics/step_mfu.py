"""Whole training step's share of the chips' bf16 peak: the model FLOPs
per env-step (``work/<algo>.py``, from the configuration's widths) times
the env-steps trained per second over the traced window, over chips x
peak."""
from bench import harness, rooflines


def read(ctx):
    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    flops = harness.load_module(ctx.bench / "work" / f"{cfg['algo']}.py"
                                ).flops_per_env_step(cfg, traffic)
    rate = ctx.window["env_steps"] / ctx.window["seconds"]
    peak = rooflines.peaks(ctx.bench, ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops * rate / (ctx.cell.chips * peak)
