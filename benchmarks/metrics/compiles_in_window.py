"""Compile plane: real compiles (jax monitoring events, persistent-cache
loads excluded) between the window's opening and its end; 0 is the steady
state."""


def read(run):
    if run.compiles_before is None or run.compiles_after is None:
        return None
    return (run.compiles_after["real_compiles"]
            - run.compiles_before["real_compiles"])
