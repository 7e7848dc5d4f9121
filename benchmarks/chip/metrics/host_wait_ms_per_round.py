"""Time the engine's dispatch thread waited for the prefetch thread to
stage the next chunk (the program's ``host_wait_s`` counter of the timed
fit, which includes staging its first chunk), per round of the fit."""
LAYER = "engine loop"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "rounds_per_s"


def read(obs):
    wait = obs.stats.get("host_wait_s")
    if wait is None:
        return None
    return 1000.0 * wait / obs.rounds
