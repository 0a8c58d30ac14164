"""Adapters to the system under test: the only modules of the benchmark
that touch the program's classes."""


def install_weights(model, spec, leaves):
    """Put ``leaves`` (name -> device array, made by the benchmark from
    the seed) into ``model``'s parameters, holding the program to the
    reference's names and shapes. ``leaves`` is emptied: on a full chip
    a second reference to the weights is a second copy's worth of
    memory that cannot be reused."""
    params = model.named_parameters_dict()
    if set(params) != set(spec):
        raise SystemExit(
            "perfbench: the program's parameters differ from the "
            f"reference's: {sorted(set(params) ^ set(spec))[:6]}")
    for name, p in params.items():
        if tuple(p.shape) != tuple(spec[name][0]):
            raise SystemExit(f"perfbench: {name} is {tuple(p.shape)} in the "
                             f"program, {spec[name][0]} in the reference")
        p._data = leaves.pop(name)
