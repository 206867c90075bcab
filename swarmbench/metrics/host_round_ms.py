"""Session layer: mean host time of one `SwarmSession.round` call in the
window (placing the host batch block and enqueueing the round), on the
harness's clock around its ``dispatch`` span."""


def read(ctx):
    spans = ctx["dispatch_s"]
    return sum(spans) / len(spans) * 1e3 if spans else None
