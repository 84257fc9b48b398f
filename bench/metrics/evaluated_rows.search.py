"""Rows the program evaluated (its ``GATrainer.unique_evals`` counter)
over the nominal rows: every child of every generation and the initial
population. Dedup and the evaluation cache are what push it below 100."""


def read(ctx):
    r = ctx["result"]
    return 100.0 * r["evaluated_rows"] / r["nominal_rows"]
