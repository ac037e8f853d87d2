"""Traffic kind ``lmpretrain_routed_tokens``: ``lmpretrain_routed``'s job,
phases, window, trace and result line, unedited, under
``lmpretrain_routed_placed``'s COMPARISON, unedited, for a routed model WHOSE
ROUTER TAKES A BIAS. Of what that driver adds to ``lmpretrain_routed``, its
(2) and (3) are taken as they are and its (1) is left out:

(1) NOT HERE: set-up places no experts. A balancing rule moves this router's
bias (``hybridlm_optimizer``'s, on every ``expert_bias`` leaf), which is the
source's own answer to an uneven load; a placement by the seeded routers'
load beside it would be a second answer that the source does not give, and a
model with a multi-token-prediction module refuses one.

(2) THE FOURTH ARITHMETIC GAP, ``token_loss_rms``
(``lmpretrain_routed_placed._mode_gaps``): the root mean square over the
batch's tokens of the program's loss OF EACH TOKEN less the reference's.
``loss_abs`` is the gap of two MEANS: a bf16 model's rounding cancels in it
by chance, and the bf16 reference's own mean is one bf16 number, as near the
float32 one as the rounding of a value near 13 happens to fall (anywhere in
0 .. 0.031). Token by token nothing cancels. ``as_run`` and ``matched`` hold
it under their limits; the reference gives ``token_losses``.

(3) THE SECOND READING WITHIN THE HOST'S MEMORY (``--check-seeds``,
``lmpretrain_routed_placed.second_reading``): the bf16 reference's four gaps
and its own epoch after the float32 replays have returned, their leaves on
disk meanwhile. It must be refused by one of the FOUR limits of ``as_run``
or by the step's.

No line of the comparison is this file's: ``run_phase`` binds the one name
``lmpretrain_routed_placed.run_phase`` binds, without its ``_start``."""

from __future__ import annotations

from benchmark.drivers import lmfit, lmpretrain  # noqa: F401 - a driver's
from benchmark.drivers import lmpretrain_routed as routed
from benchmark.drivers.lmpretrain import (  # noqa: F401 - a driver's surface
    MODES, STEP_GAPS, _named, check_phases, phases)
from benchmark.drivers.lmpretrain_routed import (  # noqa: F401
    SELECTION, RoutedReference)
from benchmark.drivers.lmpretrain_routed_placed import (  # noqa: F401
    GAPS, _mode_gaps, bf16_reference_gaps, check_objective)


def run_phase(ctx) -> None:
    lmpretrain.check_objective = check_objective
    if ctx.trace:
        routed._note_the_stretchs_fences()
    lmpretrain.run_phase(ctx)
