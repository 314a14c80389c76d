"""The algorithm's work per admitted-or-rejected request, from the
configuration's shapes, whatever implementation runs: one hash of
2*(d+1)*K*L flops (counted once, at any precision), L words of the tenant's
tables looked up, L words incremented (read and written), and the request's
(seq, d_model) float32 embedding read."""


def per_item(cfg: dict) -> dict:
    K, L, d = cfg["num_bits"], cfg["num_tables"], cfg["d_model"]
    word = 4
    return {"flops": 2 * (d + 1) * K * L,
            "bytes": L * word + 2 * L * word + cfg["seq"] * d * 4}
