"""A reader added as a file: decode steps per prefill over the window."""


def steps_per_request(sources, params):
    c = sources.get("serve", {}).get("counters")
    if not c or not c["decode_prefills"]:
        return None
    return c["decode_steps"] / c["decode_prefills"]
