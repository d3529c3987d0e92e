"""The check sees faults of the timed path: a run whose path is broken
underneath reads `correct` false. The device check is skipped (the run goes
through `run.run_cell` on the CPU at a tiny size); everything else is a
whole run. The exchange between chips is left out of this list: no cell
of the benchmark spans chips."""
import jax.numpy as jnp
import numpy as np
from repro.core.gibbs import GibbsSampler
from repro.serve.cluster import ClusterCoordinator
from repro.serve.frontend import RecommendFrontend


def test_sound_runs_are_correct(tiny):
    """On the CPU (float32 at full precision) the program and the float64
    reference agree to rounding: the reference follows the sampler's
    conditionals and random streams, not just their distribution."""
    train = tiny("chembl-train")
    assert train["correct"]
    assert train["compared"]["draw_gap"]["value"] < 1e-4
    assert train["compared"]["hyper_gap"]["value"] < 1e-4
    serve = tiny("ml20m-serve-mixed")
    assert serve["correct"]
    assert serve["compared"]["score_gap"]["value"] < 1e-4


def test_train_state_unchanged(tiny, monkeypatch):
    monkeypatch.setattr(GibbsSampler, "sweep", lambda self, state: state)
    out = tiny("chembl-train")
    assert not out["correct"]


def test_train_half_the_ratings_left_out(tiny, monkeypatch):
    """Every bucket's second half of rows loses its ratings (mask 0)."""
    real = GibbsSampler.sweep

    def half(self, state):
        def cut(b):
            rows = b.mask.shape[0]
            keep = (jnp.arange(rows) < rows // 2)[:, None]
            return b._replace(mask=b.mask * keep, values=b.values * keep)

        items, users = self._plan_args[0]
        plans = (tuple(cut(b) for b in items), tuple(cut(b) for b in users))
        return self._sweep(state, plans)

    monkeypatch.setattr(GibbsSampler, "sweep", half)
    assert not tiny("chembl-train")["correct"]
    monkeypatch.setattr(GibbsSampler, "sweep", real)


def test_train_one_draw_altered(tiny, monkeypatch):
    """The heaviest user's draw is moved where the sweep produces it."""
    real = GibbsSampler.sweep

    def altered(self, state):
        out = real(self, state)
        deg = np.zeros(self.m, np.int64)
        for b in self.user_plan_host.buckets:
            np.add.at(deg, b.item_ids, b.mask.sum(1).astype(np.int64))
        return out._replace(u=out.u.at[int(np.argmax(deg))].add(0.5))

    monkeypatch.setattr(GibbsSampler, "sweep", altered)
    assert not tiny("chembl-train")["correct"]


def test_serve_answer_altered(tiny, monkeypatch):
    real = ClusterCoordinator._serve

    def altered(self, topk, **kw):
        vals, idx = real(self, topk, **kw)
        idx = idx.copy()
        idx[:, 0] = (idx[:, 0] + 1) % self._snapshot()[1].n_items
        return vals, idx

    monkeypatch.setattr(ClusterCoordinator, "_serve", altered)
    assert not tiny("ml20m-serve-mixed")["correct"]


def test_serve_half_the_batch_left_out(tiny, monkeypatch):
    real = RecommendFrontend.flush

    def half(self):
        res = real(self)
        return res[: len(res) // 2]

    monkeypatch.setattr(RecommendFrontend, "flush", half)
    out = tiny("ml20m-serve-mixed")
    assert out["failed"] > 0 and not out["correct"]
