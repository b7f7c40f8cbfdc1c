"""Engine: the longest stretch of the window in which no stream at all
received a token, at the client. Where the lanes are always full this is a
stall of the whole decode loop (a step, or the pump between two steps):
0.2 s in a sound run of ``serve-batch-long``, 11 s in the slow ones."""


def read(run):
    v = run.get("client", {}).get("stall_max_s")
    return None if v is None else v * 1e3
