"""Mean over the window's decode steps of the slots decoding over the
slots there are."""


def read(run):
    steps = [s for s in run.window_steps() if s.contexts]
    if not steps:
        return None
    return sum(len(s.contexts) / run.slots for s in steps) / len(steps)
