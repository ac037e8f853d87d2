"""``kernel.moe_gmm_roofline``: share of its roofline of the expert layers'
grouped matrix product: the least time the chip could take for the pairs
routed to the held experts in the steps traced over the summed device time
of the product's kernels (``harness/moe_costs.gmm_seconds``: forward,
recomputed and backward calls by the kernel's name, whatever implements it).
Needed work: per pair the two products forward and their four backward
(``moe_costs.gmm_pair``), each held expert's weights read once a forward and
read and their gradient written once a backward, an expert layer and step;
the recomputed forward is not needed work. The pairs are the program's own
count OF THE TRACED STRETCH (``values["moe_pairs_in_trace"]``: the growth of
its counter ``model.experts.pairs_held`` between the stretch's first and
last fence; the training steps' report, summed inside the epoch program),
and the steps it reported there must be the steps traced. The stretch's
evaluations run the forward products too: their calls are in the device time
and in no needed work (24 of 312 calls in the cell's stretch), so the share
reads low by that, never high. None without a trace, a count of that stretch
or the kernels."""

from benchmark.harness import costs, moe_costs


def read(sources):
    trace = sources.get("trace")
    values = sources.get("values", {})
    kernel = sources.get("kernels", {}).get("moe_gmm")
    steps, pairs = values.get("steps_in_trace"), values.get("moe_pairs_in_trace")
    if (trace is None or not steps or not pairs or not kernel
            or values.get("moe_steps_reported_in_trace") != steps):
        return None
    seconds = moe_costs.gmm_seconds(trace.ops)
    if seconds <= 0:
        return None
    weights = steps * kernel["layers"] * kernel["weights_per_layer_step"]["bytes"]
    needed = {"flops": pairs * kernel["per_pair"]["flops"],
              "bytes": pairs * kernel["per_pair"]["bytes"] + weights}
    return 100.0 * costs.roofline(needed, sources["peaks"])["min_s"] / seconds
