"""Share of the device's busy time in the traced window spent in prefill
programs (the monolithic ``prefill_write`` and the chunked
``chunk_step``), in percent."""

PROGRAMS = ("prefill_write", "chunk_step")


def read(r):
    t = r.trace
    if t.busy_s <= 0 or not any(p in t.programs
                                             for p in PROGRAMS):
        return None
    return 100.0 * t.device_s(*PROGRAMS) / t.busy_s
