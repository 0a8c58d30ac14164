"""Share of the engine's slots that held a request, averaged over the
engine's steps from the end of warm-up to the end of the drain, lead-in
included (engine ``stats()`` before and after: inside the window the
call would hold the engine). %"""


def read(facts):
    a, b = facts.get("engine_start"), facts.get("engine_end")
    if not a or not b or b["engine_steps"] == a["engine_steps"]:
        return None
    steps = b["engine_steps"] - a["engine_steps"]
    return 100.0 * (b["slot_steps"] - a["slot_steps"]) / (steps * b["slots"])
