"""attention_kernel_ms: device ms per step of the training flash attention
(``kernels/flash_attention.py``), forward and backward: the ops whose
``op_name`` path carries the inner scope ``flash_fwd`` (the forward kernel
with its log-sum-exp) or ``flash_bwd`` (the dq and dk/dv kernels and the
row sums they read), by self time over the traced window (``scopes.py``).
Both scopes sit inside ``attention``, so this time is part of
``attention_ms``. None where no op carries either name: a program that
does not run the kernel."""
import scopes
from devtrace import window

NAMES = ("flash_fwd", "flash_bwd")


def _is_flash(op_name: str) -> bool:
    for part in op_name.split("/"):
        m = scopes.UNWRAP.match(part)
        if m and m.group(2) in NAMES:
            return True
    return False


def read(ctx):
    tr = scopes.trace(ctx)
    if tr is None:
        return None
    lo, hi = window(tr)
    keep = set(ctx.ops) if getattr(ctx, "ops", None) else None
    devs = [d for d in sorted(tr.ops) if keep is None or d in keep]
    ns, found = 0.0, False
    for d in devs:
        for name, t in scopes.self_times(tr.ops[d], lo, hi).items():
            if _is_flash(name):
                ns, found = ns + t, True
    if not found:
        return None
    return ns / max(len(devs), 1) / max(ctx.steps, 1) / 1e6
