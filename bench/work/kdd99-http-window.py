"""The algorithm's work per streamed item over a sliding window, from the
configuration's shapes, whatever implementation runs: one hash of
2*(d+1)*K*L flops (counted once, at any precision); L float32 tail words and
L live words looked up, L live words incremented (read and written), and the
raw row read; and, once per epoch, amortised over its ``epoch_rows`` items, a
rotation that reads the old live epoch, the expiring epoch and the tail,
writes the tail and zeroes one epoch (5 table sweeps).  A second gather of
the live words after the insert is an implementation's choice and is not
counted."""


def per_item(cfg: dict) -> dict:
    K, L, d = cfg["num_bits"], cfg["num_tables"], cfg["dim"]
    word = 4
    table = L * (1 << K) * word
    return {"flops": 2 * (d + 1) * K * L,
            "bytes": (L * word + L * word + 2 * L * word + d * 4
                      + 5 * table / cfg["epoch_rows"])}
