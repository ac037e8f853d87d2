"""``model.exit_loss_ms``: device milliseconds a training step spends in
operations whose RESULT carries the vocabulary axis (the exits' logits, their
softmax statistics' inputs, the logits' gradient, the head's weight gradient
and its update; not the product that carries the gradient back to the hidden
state, whose result has no such axis): their summed device time in the traced
stretch over the steps the program counted as completed there
(``values["steps_in_trace"]``, from ``estimator.steps_completed`` between the
two fences that bracket the trace). The driver gives the vocabulary's size
(``values["vocab_size"]``). None without a trace or a count."""

import re

from benchmark.harness import xplane


def read(sources):
    trace = sources.get("trace")
    values = sources.get("values", {})
    steps, vocab = values.get("steps_in_trace"), values.get("vocab_size")
    if trace is None or not steps or not vocab:
        return None
    axis = re.compile(rf"[\[,]{int(vocab)}[\],]")
    seconds = sum(total for name, (_, total) in trace.ops.items()
                  if axis.search(xplane.result_type(name)))
    return 1e3 * seconds / steps if seconds > 0 else None
