"""Host time the prefetch thread spent sampling clients and gathering
their batches into the staging pool (the program's ``stage_sample_s``
counter of the timed fit, the ``prefetch.stage.sample`` spans; with the
paged EF store, the host page gather too), per round of the fit."""
LAYER = "engine loop"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "rounds_per_s"


def read(obs):
    sample = obs.stats.get("stage_sample_s")
    if sample is None:
        return None
    return 1000.0 * sample / obs.rounds
