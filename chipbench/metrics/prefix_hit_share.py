"""Share of prompt tokens the scheduler served from cached prefix blocks
over the window: the program's ``hit_tokens`` / (``hit_tokens`` +
``miss_tokens``) counters, as they moved in the window, in percent."""


def read(r):
    hit = r.data.get("prefix_hit_tokens")
    miss = r.data.get("prefix_miss_tokens")
    if hit is None or miss is None or hit + miss <= 0:
        return None
    return 100.0 * hit / (hit + miss)
