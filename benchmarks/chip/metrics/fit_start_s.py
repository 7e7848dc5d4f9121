"""Seconds from the timed fit's entry to the end of its first chunk's
dispatch (the program's ``fit_start_s`` counter): the fit's own start
inside ``setup_s`` — building the engine, allocating and staging the EF
table (``ef.init``), staging the first chunk, and the first dispatch's
trace, lowering and compile-cache load."""
LAYER = "trainer facade"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(obs):
    return obs.stats.get("fit_start_s")
