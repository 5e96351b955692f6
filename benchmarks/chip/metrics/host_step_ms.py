"""host_step_ms: mean host ms of one ``GossipTrainer.step`` call, the
``train_step`` span that ``api/trainer.py`` opens on the profiler's host
clock, over the spans inside the traced window (``scopes.py``): dispatch,
metric normalisation and the observer hook, without the caller's time."""
import scopes


def read(ctx):
    tr = scopes.trace(ctx)
    spans = scopes.host_steps(tr) if tr is not None else []
    return sum(spans) / len(spans) / 1e6 if spans else None
