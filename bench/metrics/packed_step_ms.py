"""Device time per run of the unified packed-tick program
(``jit_step_unified_fn``), from the device trace."""


def read(run):
    if run.reduction is None:
        return None
    seconds, calls = run.reduction.program("unified")
    return 1e3 * seconds / calls if calls else None
