"""Wall seconds the engine had spent lowering and compiling its AOT
programs when the window opened (``stats()`` ``compile_s`` in
``counters.before``: a level, not a delta): the part of ``setup_s`` that a
warm compile cache removes."""


def read(run):
    return run["counters"]["before"].get("compile_s")
