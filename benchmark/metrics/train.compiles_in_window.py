"""train.compiles_in_window: backend compilations counted by a
``jax.monitoring`` listener while the window ran.  It should read 0."""


def read(view):
    return view.raw["compiles"]
