"""Non-padding tokens of all optimizer steps completed in the window,
over the window's whole wall time, over chips."""


def read(facts):
    work = facts["work"]
    if not work.get("steps"):
        return None
    return work["tokens"] / facts["window_s"] / facts["chips"]
