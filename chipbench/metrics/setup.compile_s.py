"""Seconds the program spent compiling before the traced window (jaxpr
tracing, lowering and backend compile, which includes loading a program
from the persistent compilation cache): the program's compile-seconds
counter as it stood at its first recorded span. Compiles inside the
window are ``compile`` spans, and those after it are not counted. None
where the program keeps no such counter."""


def read(obs):
    try:
        from repro.launch.trace import compile_seconds_before_recording
    except ImportError:
        return None
    return compile_seconds_before_recording() or None
