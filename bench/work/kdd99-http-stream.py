"""The algorithm's work per streamed item, from the configuration's shapes,
whatever implementation runs: one hash of 2*(d+1)*K*L flops (counted once,
at any precision), L table words looked up, L words incremented (read and
written), and the raw row read."""


def per_item(cfg: dict) -> dict:
    K, L, d = cfg["num_bits"], cfg["num_tables"], cfg["dim"]
    word = 4
    return {"flops": 2 * (d + 1) * K * L,
            "bytes": L * word + 2 * L * word + d * 4}
