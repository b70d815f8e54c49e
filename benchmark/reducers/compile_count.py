"""Programs built (compiled, or loaded from the cache) inside the
window, as ``jax.monitoring``'s backend-compile events counted them."""


def reduce(args, rctx):
    return float(rctx["window_compiles"])
