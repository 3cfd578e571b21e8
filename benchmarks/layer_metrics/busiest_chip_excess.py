"""device: how far the busiest chip of the mesh stands above the
average one in the recorded stretch: 100 x (max / mean of
``busy_s_by_device`` - 1). 0 where every chip is busy alike; a
statement ends when its busiest chip does, so the excess is time the
other chips wait (ROADMAP A9's imbalance as a number). One chip, or a
stretch in which no chip ran anything, gives nothing to read."""


def read(ctx):
    trace = ctx["trace"]
    busy = (trace or {}).get("busy_s_by_device") or ()
    if len(busy) < 2 or not sum(busy):
        return None
    return 100.0 * (max(busy) / (sum(busy) / len(busy)) - 1.0)
