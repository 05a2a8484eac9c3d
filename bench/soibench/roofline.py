"""A kernel's share of its roofline, from a traced run."""


def share(run, kernel: str):
    """100 x (sum of the least times of the kernel's calls) / (sum of the
    times the trace gives them); None when the trace holds no call, or a
    call whose operand shapes it does not give."""
    k = run.trace and run.trace.kernels.get(kernel)
    if not k or not k[0] or k[2] is None or not k[1]:
        return None
    return 100.0 * k[2] / k[1]
