"""Engine runtime: idle time of the first device a launch while the host was in `launch`
or one of its three children, each gap split by overlap.  Read from shortened, blocking
replays of the run's last launch, back to back (`_explain.py`): what the program's one
launch leaves the device waiting for, not the harness's window."""

from benchmark.layers._explain import LAUNCH_SPANS, idle_ms


def read(ctx):
    return idle_ms(LAUNCH_SPANS)
