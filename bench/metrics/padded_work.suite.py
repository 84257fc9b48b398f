"""Share of the suite's fitness work that falls on padding: 100 × (1 −
real ÷ run shift-add terms), from the program's counter
``SuiteResult.fitness_terms`` summed over the window's calls. Real terms
are each cell's own rows × its own topology, run terms the padded
topology × its bucket's sample bound in whole kernel tiles."""


def read(ctx):
    terms = ctx["result"].get("fitness_terms")
    if not terms:
        return None
    real, run = terms
    return 100.0 * (1.0 - real / run)
