"""Share of the roofline reached by the plan-grid argmin kernel
(``kernels/plan_grid.py``: ``plan_argmin``): the least time the chip
needs for the work of every call in the window, over their summed device
time in the trace. Bound by bytes. Moves decisions_per_s."""

from chipbench import roofline


def read(ctx):
    return roofline.roofline_share(
        ctx.calls["plan_argmin"], "plan_argmin", ctx.kernel_s.get("plan_argmin", 0.0),
        ctx.device_kind,
    )
