"""Mean device time of one jitted program, by its name in the trace's
``XLA Modules`` line. ms."""


def read(facts, module):
    red = facts.get("trace")
    if not red or not red["module_calls"].get(module):
        return None
    return 1e3 * red["module_s"][module] / red["module_calls"][module]
