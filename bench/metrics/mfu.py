"""mfu: the model FLOPs of the intervals the window completed, over the
window and the chips' bf16 peak, in percent. The FLOPs per interval come
from the configuration's shapes (``model_flops_per_interval`` in its file);
recomputed operations do not count."""


def read(ctx):
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * ctx["flops_per_interval"] * ctx["intervals"] / ctx["window_s"] / peak
