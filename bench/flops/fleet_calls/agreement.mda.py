"""In each agreement round every agent's minimum-diameter search takes
the distances of the K vectors it received."""


def calls(cfg: dict, d: int) -> list:
    return [("pairwise_dist", cfg["kappa"] * cfg["K"], (cfg["K"], d))]
