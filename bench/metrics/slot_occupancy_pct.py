"""Share of the cohort server's slot-steps that carried a live subject."""


def read(run):
    steps = run["steps"]
    if not steps:
        return None
    return 100.0 * sum(steps) / (run["slots"] * len(steps))
