"""Newton iterations per registration, from the solver's own count."""


def read(run):
    regs = run["regs"]
    return sum(r["newton_iters"] for r in regs) / len(regs) if regs else None
