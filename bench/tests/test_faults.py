"""A run with the timed path broken underneath must come out not correct.

Each fault is planted in the program, on the CPU, at a small size, and the
rest of a run (priming, the window, the checks) is driven as on the chip:

  unchanged   a refresh that returns its state unchanged: the ingestor
              reads nothing new, so acknowledged commits never serve;
  half        half of each packed batch left out: the engine answers the
              first half of the lanes and leaves the rest at 1;
  altered     an answer altered where it is produced: every /cost body's
              first join cardinality is off by 1%.

A cell on one chip has no exchange between chips to leave out.
"""
import pytest


def _unchanged(monkeypatch):
    from repro.service.ingest import AsyncIngestor

    original = AsyncIngestor._scatter_gather

    def scatter_gather(self):
        # The start-up scan reads the dataset; every later one hands back
        # the files the catalog already holds.
        if not self.catalog.scanned:
            return original(self)
        return [], list(self.catalog.entry_fingerprints())

    monkeypatch.setattr(AsyncIngestor, "_scatter_gather", scatter_gather)


def _half(monkeypatch):
    import jax.numpy as jnp

    from repro.engine import EstimationEngine

    original = EstimationEngine.estimate

    def estimate(self, batch, schema_bound=None, *, mode="paper"):
        out = original(self, batch, schema_bound, mode=mode)
        half = batch.batch // 2
        return out._replace(ndv=out.ndv.at[half:].set(jnp.float32(1.0)))

    monkeypatch.setattr(EstimationEngine, "estimate", estimate)


def _altered(monkeypatch):
    import repro.fleet.router as router_mod

    original = router_mod.compute_cost

    def compute_cost(*args, **kwargs):
        body = original(*args, **kwargs)
        if body["joins"]:
            body["joins"][0]["cardinality"] *= 1.01
        return body

    monkeypatch.setattr(router_mod, "compute_cost", compute_cost)


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["unchanged", "half", "altered"])
def test_fault_is_not_correct(small, monkeypatch, fault):
    fault(monkeypatch)
    out = small("tpcds_sf1000.maintenance")
    assert not out["correct"], out["checks"]


def test_sound_run_is_correct(small):
    out = small("tpcds_sf1000.maintenance")
    assert out["correct"], out["checks"]
