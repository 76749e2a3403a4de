"""Whole runs on the CPU at a tiny size, past the harness's look for a
chip: a sound run is correct, and each fault planted under the timed path
makes ``correct`` false."""

import pytest

import tiny


@pytest.fixture(scope="module")
def run():
    return tiny.load_run()


def _go(run, kind, fault=None, seed=2**33 + 7, seconds=1.5):
    import jax
    return run.run_cell(tiny.spec(kind), seed, seconds, False, jax,
                        jax.devices()[:1], log=lambda *a: None, fault=fault)


def token_altered(cls):
    """Every fifth decode tick emits (and feeds back) the next token id
    instead of the one the model chose."""
    class Faulty(cls):
        n = 0

        def _on_decode_tick(self, now, did):
            d = self.dstates[did]
            before = {r: len(self.outputs[r]) for r in d.meta}
            super()._on_decode_tick(now, did)
            Faulty.n += 1
            if Faulty.n % 5:
                return
            for r, n0 in before.items():
                out = self.outputs[r]
                if len(out) > n0:
                    out[-1] = (out[-1] + 1) % self.cfg.vocab_size
                    if r in d.meta:
                        d.meta[r].last_token = out[-1]
    return Faulty


def state_unchanged(cls):
    """The decode step's cache length does not advance: each token's K/V
    overwrites the previous one's slot."""
    class Faulty(cls):
        def _on_decode_tick(self, now, did):
            d = self.dstates[did]
            before = {r: len(self.outputs[r]) for r in d.meta}
            super()._on_decode_tick(now, did)
            for r, n0 in before.items():
                if r in d.meta and len(self.outputs[r]) > n0 and n0 > 1:
                    d.meta[r].cache_len -= 1
    return Faulty


def history_masked(cls):
    """Attention sees only the last 64 tokens (a sliding window the
    configuration does not have)."""
    import dataclasses

    class Faulty(cls):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.ctx = dataclasses.replace(self.ctx, window=64)
    return Faulty


def test_split_prompt_is_compared(run):
    """A fan-out batch: the planner splits its longest prompt (request 7,
    12,749 tokens), whose second chunk runs over paged history, and the
    comparison always holds that request."""
    out = _go(run, "fanout", seconds=6.0)
    assert out["info"]["chunks_over_history"] >= 1
    assert out["info"]["multi_chunk_requests"] >= 1
    assert 7 in out["info"]["compared"]
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_sound_run_is_correct(run, kind):
    out = _go(run, kind)
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] > 0
    assert out["info"]["window_compiles"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   history_masked])
@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_planted_fault_is_not_correct(run, kind, fault):
    out = _go(run, kind, fault)
    assert not out["correct"], out["checks"]
