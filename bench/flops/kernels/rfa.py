"""rfa (``repro.kernels.rfa``): the smoothed Weiszfeld geometric median
of K vectors of length d, run in weight space on their Gram matrix."""

WRAPPERS = ("rfa_pallas",)


def ops_bytes(K: int, d: int, n_iter: int) -> tuple:
    """The Gram matrix, n_iter steps on it (a K x K product, the
    distances, the reweighting), then the weighted sum of the K vectors;
    reads the stack twice (float32) and writes one vector of length d."""
    gram = 2 * K * K * d
    steps = n_iter * (2 * K * K + 8 * K)
    combine = 2 * K * d
    return gram + steps + combine, 4 * (2 * K * d + d)
