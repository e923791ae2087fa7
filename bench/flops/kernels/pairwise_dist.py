"""pairwise_dist (``repro.kernels.pairwise_dist``): squared distances of
K vectors of length d through their Gram matrix."""

#: the jitted functions that call the kernel, as HLO op names show them
WRAPPERS = ("gram", "pairwise_sq_dists_pallas")


def ops_bytes(K: int, d: int) -> tuple:
    """The K x K x d multiply-adds and the K x K combination with the
    diagonal; reads the K x d stack once (float32) and writes K x K."""
    return 2 * K * K * d + 3 * K * K, 4 * (K * d + K * K)
