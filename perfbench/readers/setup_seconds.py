"""Process start to the first measured request or step."""


def read(facts):
    return facts.get("setup_s")
