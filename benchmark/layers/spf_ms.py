"""Engine step: device time a launch of the shortest-path stage (Bellman-Ford, next
hops, path walk), which runs outside the loop, replica-independent: the part of
`outside_scopes_ms` under the scopes whose last component is `spf`.  None where the
program reads no such time (another engine, or a program without the split).  Read from
shortened replays of the run's last launch, not from the measured window
(`_explain.py`)."""

from benchmark.layers._explain import table


def read(ctx):
    got = table()
    scopes = None if got is None else got.get("outside_scopes_ms")
    chosen = [ms for scope, ms in (scopes or {}).items()
              if scope.rsplit(".", 1)[-1] == "spf"]
    return sum(chosen) if chosen else None
