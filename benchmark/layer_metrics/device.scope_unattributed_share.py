"""``device.scope_unattributed_share``: percent of the traced stretch's
summed operation time that ``harness/scopes.py`` could give to no scope: the
operations in no live program's map (a program nobody noted, one already
collected), those whose instruction carries no registered scope (copies and
prefetches the compiler made, a loop's own counter, an ``op_name`` a compiler
pass cut short) and those two programs know under different scopes
(``ambiguous``). What every ``*_scope_ms`` metric cannot see, in the open;
``scopes.py`` prints the five heaviest. None without a trace or a program
that gives the map."""

from benchmark.harness import scopes


def read(sources):
    made = scopes.table(sources)
    share = None if made is None else made.blind_share()
    return None if share is None else 100.0 * share
