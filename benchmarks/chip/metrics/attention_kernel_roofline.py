"""attention_kernel_roofline: the training flash attention's share of its
roofline (``roofline.py``), forward and backward.

Its time is ``attention_kernel_ms``'s: the self time of the ``flash_fwd`` and
``flash_bwd`` ops. Its operations are 3 x the ``attention`` term of the
family's own count (``flops.py``; every layer, causal pairs only) times the
tokens of a step, W x B x S, the count ``step_mfu`` takes its share from. Its
bytes: forward, q, k and v read and o written; backward, q, k, v, o and dO read
and dq, dk and dv written. Each is a [B, S, heads, head_dim] tensor of 2-byte
elements, with the query heads for q, o, dO and dq and the key/value heads for
the rest, for each of W replicas and every layer. None where the program runs
no such kernel."""
from pathlib import Path

import flops
import harness
import roofline

KERNEL = harness._module(Path(__file__).with_name("attention_kernel_ms.py"),
                         "metric_attention_kernel_ms")


def work_per_step(config: dict, traffic: dict) -> tuple:
    """(operations, bytes) the kernels require in one step of the cell."""
    W, B, S = traffic["workers"], traffic["per_worker_batch"], traffic["seq"]
    H, Hkv = config["num_heads"], config["num_kv_heads"]
    hd = config.get("head_dim") or config["d_model"] // H
    ops = 3.0 * flops.forward_terms(config, S)["attention"] * W * B * S
    q, kv = 2 * B * S * H * hd, 2 * B * S * Hkv * hd   # bytes of one tensor
    forward = 2 * q + 2 * kv                           # q, k, v read; o written
    backward = 4 * q + 4 * kv     # q, k, v, o, dO read; dq, dk, dv written
    return ops, W * config["num_layers"] * (forward + backward)


def read(ctx):
    ms = KERNEL.read(ctx)
    if ms is None:
        return None
    ops, nbytes = work_per_step(ctx.config, ctx.traffic)
    return roofline.share(ctx, ms * 1e6, ops / ctx.chips, nbytes / ctx.chips)
