"""Host seconds the optimizer (MCTS over the rewrite rules) took."""


def read(rec):
    return rec["optimize_s"]
