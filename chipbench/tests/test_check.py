"""``correct`` comes out true on a sound run, and false under the float8
control and under each fault the cells can have, planted in the timed path
underneath a whole run (the harness's look for a chip skipped)."""
import numpy as np
import pytest
from conftest import TINY_LIMIT, run_tiny

CODE, REASONING = "danube3-4b.code", "qwen3-14b-tp4.reasoning"


@pytest.mark.parametrize("workload", [CODE, REASONING])
def test_sound_run_is_correct_and_control_is_not(tiny_bench, workload):
    res = run_tiny(tiny_bench, workload, control=True)
    c = res["checked"]
    assert res["correct"], c
    assert c["wrong_lengths"]["value"] == 0
    assert c["widest_gap"]["value"] <= TINY_LIMIT
    assert c["control_gap"]["value"] > TINY_LIMIT
    assert c["control_gap"]["value"] >= 3 * c["widest_gap"]["value"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checked"


def state_unchanged(mp, vocab):
    from repro.core.runner import JaxRunner
    inner = JaxRunner._masked_decode

    def broken(self, params, state, tokens, active):
        logits, _ = inner(self, params, state, tokens, active)
        return logits, state
    mp.setattr(JaxRunner, "_masked_decode", broken)


def half_batch(mp, vocab):
    """Half of the decoding requests left out: they are handed the tokens
    of the other half."""
    from repro.core.runner import JaxRunner
    inner = JaxRunner.decode

    def broken(self, reqs):
        out = inner(self, reqs)
        h = len(out) // 2
        return out[:len(out) - h] + out[:h]
    mp.setattr(JaxRunner, "decode", broken)


def token_altered(mp, vocab):
    from repro.core.runner import JaxRunner
    inner = JaxRunner.decode

    def broken(self, reqs):
        out = inner(self, reqs)
        return [(out[0] + 1) % vocab] + out[1:] if out else out
    mp.setattr(JaxRunner, "decode", broken)


def ends_early(mp, vocab):
    """The scheduler ends every request one token short of its budget."""
    from repro.core.request import Request
    mp.setattr(Request, "done", property(
        lambda self: self.generated >= self.max_new_tokens - 1))


def exchange_left_out(mp, vocab):
    """No all-reduce after the sharded projections: each output is the
    first chip's partial sum alone, as if the other three's never came."""
    import jax
    from repro.core.runner import JaxRunner
    inner = JaxRunner.__init__

    def broken(self, cfg, params, ctx, **kw):
        st = dict(params["dense_stack"])
        h, f = st["wo"].shape[1], st["w_down"].shape[1]
        st["wo"] = st["wo"].at[:, h // 4:].set(0)
        st["w_down"] = st["w_down"].at[:, f // 4:].set(0)
        params = dict(params, dense_stack=st)
        jax.block_until_ready(params)
        inner(self, cfg, params, ctx, **kw)
    mp.setattr(JaxRunner, "__init__", broken)


FAULTS = [(CODE, state_unchanged), (CODE, half_batch), (CODE, token_altered),
          (CODE, ends_early), (REASONING, state_unchanged),
          (REASONING, half_batch), (REASONING, token_altered),
          (REASONING, ends_early), (REASONING, exchange_left_out)]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w.split('.')[1]}-{f.__name__}"
                              for w, f in FAULTS])
def test_fault_in_the_timed_path_is_not_correct(tiny_bench, monkeypatch,
                                                workload, fault):
    fault(monkeypatch, 256)
    res = run_tiny(tiny_bench, workload, seed=2**31 + 17)
    assert not res["correct"], res["checked"]
    assert np.isfinite(res["checked"]["widest_gap"]["value"])


def test_sample_reaches_its_tokens_at_the_code_mix_on_every_seed():
    """At the code cell's own schedule, with every request due in a 50 s
    window finished, each seed's sample holds ``MIN_TOKENS`` served
    tokens in ``MAX_POSITIONS`` positions, and always the longest."""
    import json
    import os
    import types
    from chipbench import check, traffic
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "traffic", "code.json")) as f:
        mix = json.load(f)
    recs = []
    for r in traffic.generate(mix, 50, 32000, 4096, 1):
        handle = types.SimpleNamespace(output=[0] * r.max_new, t_finished=1.0)
        recs.append(types.SimpleNamespace(req=r, handle=handle, finished=True,
                                          tokens=handle.output))
    longest = max(len(r.req.prompt) + r.req.max_new for r in recs)
    for seed in range(2**31, 2**31 + 300):
        items = check.sample(recs, seed)
        assert sum(len(t) for _, t in items) >= check.MIN_TOKENS
        assert len(items) >= check.MIN_REQUESTS
        assert sum(len(p) + len(t) - 1 for p, t in items) <= \
            check.MAX_POSITIONS
        assert len(items[0][0]) + len(items[0][1]) == longest
