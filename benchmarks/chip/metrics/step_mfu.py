"""Model FLOPs of the traced window's steps over (window seconds x chips x
the chip's bf16 peak), from the device trace's window.  The FLOPs are
``flops.step_flops``: 6 per matmul parameter per token plus PaLM's
attention term; recomputed work does not count."""


def read(context, variant=None):
    trace = context.get("trace")
    window = context.get("window")
    if not trace or not window or window["steps"] == 0 or trace["window_s"] <= 0:
        return None
    done = window["steps"] * context["step_flops"]
    return done / (trace["window_s"] * context["chips"] * context["peak"]["bf16_flops_per_s"])
