"""Hessian matvecs (PCG iterations) per registration, from the solver's count."""


def read(run):
    regs = run["regs"]
    return sum(r["matvecs"] for r in regs) / len(regs) if regs else None
