"""Share of the window's stage-pass device calls whose decode table the
same fleet evaluation had already sent (a loop step's stage decoded
again), in percent.

While a profiler records, the program marks such a call with an
instant ``repro.sim.device_call.repeat`` span just before its
``repro.sim.device_call`` span; a program without device-call spans
reads nothing, one without the marker 0.
"""

CALL = "repro.sim.device_call"
REPEAT = CALL + ".repeat"


def read(run):
    red = run.reduction
    calls = repeats = 0
    for name, start, _ in red.host:
        if red.t0 <= start < red.t1:
            calls += name == CALL
            repeats += name == REPEAT
    if not calls:
        return None
    return 100.0 * repeats / calls
