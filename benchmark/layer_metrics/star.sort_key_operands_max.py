"""The most key operands any sort of the window's queries handed to
`lax.sort`: `maxKeyOperands` of last_execution["sort"], the largest
over the window. 1 is the aim: the TPU's compiler takes minutes over a
sort with several 64-bit key operands, so this is what the cold
set-up hangs on."""

from benchmark.layer_metrics import _sort_record


def read(ctx):
    recs = _sort_record.records(ctx)
    if not recs:
        return None
    return max(r["maxKeyOperands"] for r in recs)
