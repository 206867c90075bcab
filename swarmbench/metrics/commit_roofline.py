"""Commit kernels (`kernels/fused_merge.py`): the least time the commits
of the traced rounds could take at the chip's peaks (`swarmbench.flops`:
every site's tile read and written once, plus the wire reference on the
int8 wire), over the device time of the commit kernels' events, in
percent.

The kernels are the Pallas calls of `fused_merge_all` and
`fused_quant_merge_all`, one per parameter leaf and round; XLA names each
call after its jitted function (``fused_merge_all.23``). The rounds are
those that run whole inside the traced slice of the window (`run.window`);
their commits are the commit kernels' events in it."""

import re

from swarmbench import flops

KERNEL = re.compile(r"^fused_(quant_)?merge_all(\.\d+)?$")


def read(ctx):
    tr, per_round = ctx["trace"], ctx["commit"]
    if tr is None or not per_round["launches"] or not ctx["traced_rounds"]:
        return None
    seconds = sum(s for n, s in tr["op_s"].items() if KERNEL.match(n))
    if not seconds:
        return None
    least = ctx["traced_rounds"] * flops.least_seconds(
        per_round, flops.peak(ctx["device_kind"]))
    return 100.0 * least / seconds
