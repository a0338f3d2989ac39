"""Mean over the window's decode steps of the keys and values live in the
cache (each active slot's context) over what the slots hold
(slots x positions): the share of the reserved cache in use."""


def read(run):
    steps = [s for s in run.window_steps() if s.contexts]
    if not steps:
        return None
    cap = run.slots * run.max_len
    return sum(sum(s.contexts) / cap for s in steps) / len(steps)
