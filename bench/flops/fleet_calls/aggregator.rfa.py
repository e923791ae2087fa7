"""Every agent aggregates with one rfa call over the bucket means."""
import math


def calls(cfg: dict, d: int) -> list:
    args, K, n_byz = cfg["aggregator_args"], cfg["K"], cfg["n_byz"]
    bucket = 1 if n_byz == 0 else max(
        1, int(args["alpha_max"] / (n_byz / K)))
    return [("rfa", K, (math.ceil(K / bucket), d, args["n_iter"]))]
