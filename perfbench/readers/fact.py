"""A count the harness took as it is."""


def read(facts, key):
    return facts.get(key)
