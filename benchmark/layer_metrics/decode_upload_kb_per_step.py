"""Engine: bytes the host sent to the device while decode steps were
prepared, in kB a step: ``engine.step_upload_bytes`` / ``engine.steps`` /
1e3. Beside ``decode_uploads_per_step`` (how many transfers): the block
table goes up whole whenever one lane crossed a block (``[num_slots,
blocks_per_slot]`` int32), the packed slot state after a host write, mask
rows after a walker moved. A program that does not count the bytes gives
nothing."""


def read(run):
    sent = run["counters"].get("engine.step_upload_bytes")
    steps = run["counters"].get("engine.steps")
    if sent is None or not steps:
        return None
    return sent / steps / 1e3
