"""``estimator.optimizer_scope_ms``: device milliseconds a training step
spends under the estimator's scope ``optimizer_update`` (``tx.update`` and
``apply_updates``; in the DLRM cells the row-wise update with its write-back
kernels), over the steps traced (the program's own count where the driver
gives it, else the call count most operations share). Membership as the
PROGRAM gives it (``harness/scopes.py``: the trace's operations joined to
``obs.profiler.device_scopes()`` by instruction name). XLA fuses a leaf's
update into that leaf's weight-gradient product where nothing sums the
gradient over a loop (the Granite and routed cells) and names the fusion by
the product, so those updates are counted under the LAYER's scope and not
here (``scopes.py`` prints the share of such mixed fusions): what this reads
there is the update of the leaves whose gradient is no such product (the
embedding, the experts' weights behind their Mosaic calls, norms and
biases). ``estimator.table_update_ms`` finds the DLRM update by a result
shape the write-back kernel does not have. None without a trace, a count or
a program that gives the map."""

from benchmark.harness import scopes


def read(sources):
    return scopes.member_ms_per_step(sources, "optimizer_update")
