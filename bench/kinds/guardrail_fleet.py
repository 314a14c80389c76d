"""Test hooks of the ``guardrail_fleet`` kind, found by the configuration's
``kind`` (``tests/bench``): the sizes and the offered rate a CPU test can
hold, the control at a test size, and the faults planted in the timed
path."""
from __future__ import annotations

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "answer")


def tiny(cfg: dict, mix: dict):
    """(configuration override, mix override) at sizes a CPU test can hold;
    the semantics are the cell's own."""
    return (dict(d_model=32, num_tenants=4, num_bits=6, num_tables=8,
                 warmup_items=16, batch=16),
            dict(pool=256, rate_per_s=1500.0))


def control_numbers() -> dict:
    """The numbers compared for the reference with bf16x3 projections
    against the float64 reference, at widths where a rounding of the
    projections flips some signs."""
    from bench.reference import ace
    from bench.reference import guardrail_fleet as fleet_ref
    from bench.traffic import openloop

    pool, tid = openloop.request_pool(3, 2048, 4, 1024, 8, 1.1, 0.05)
    w = np.random.default_rng(2).standard_normal((1025, 768))
    g = np.random.default_rng(4)
    batches = [(rows, tid[rows]) for rows in
               (g.integers(0, 2048, 100) for _ in range(400))]
    out = {}
    for prec, dt in (("float64", np.float64), ("bf16x3", np.float32)):
        b = ace.hash_buckets(ace.features(pool, 0.25, dt),
                             w.astype(np.float32), 15, 50, prec)
        out[prec] = fleet_ref.replay(batches, b, num_tenants=8,
                                     num_bits=15, alpha=1.0, warmup=64)
    return fleet_ref.compare(out["bf16x3"], out["float64"])


def plant(fault: str, monkeypatch) -> None:
    """Breaks ``Guardrail.admit``, the timed path, by ``fault``."""
    import jax
    import jax.numpy as jnp

    from repro.serve.engine import Guardrail

    orig = Guardrail.admit

    def admit(self, embeds, tenant_ids=None):
        if fault == "half_batch":
            embeds = embeds.at[embeds.shape[0] // 2:].set(jnp.nan)
        kept = jax.tree.map(jnp.copy, self.state)
        out = orig(self, embeds, tenant_ids)
        if fault == "state_unchanged":
            self.state = kept
        if fault == "answer":
            out = out.copy()
            out[0] = ~out[0]
        return out

    monkeypatch.setattr(Guardrail, "admit", admit)
