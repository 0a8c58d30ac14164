"""Share of the traced window in which a collective ran on a device
and no compute did. %"""


def read(facts):
    red = facts.get("trace")
    if not red or red["n_devices"] < 2:
        return None
    return 100.0 * red["collective_exposed_s"] / red["window_s"]
