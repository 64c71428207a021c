"""JAX's trace + lower + compile (or cache load) seconds during set-up."""


def read(run, trace):
    return run["compile"]["setup"]["compile_s"]
