"""Engine runtime: idle time of the first device a launch while the host was in
`result.wait`, `.fetch` or `.unpack`: the wake-up after the device is done, the transfer.
Read from shortened, blocking replays of the run's last launch, back to back
(`_explain.py`): what the program's one launch leaves the device waiting for, not the
harness's window."""

from benchmark.layers._explain import RESULT_SPANS, idle_ms


def read(ctx):
    return idle_ms(RESULT_SPANS)
