"""decode_occupancy.decode: the engine's own counters over the traced
window: the occupancy sum (active slots / slots, per decode step) over the
decode steps."""


def read(trace, facts):
    if not facts["decode_steps"]:
        return None
    return 100.0 * facts["occupancy_sum"] / facts["decode_steps"]
