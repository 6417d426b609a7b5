"""Whole runs of the serving cell on the CPU at a small size, past the
harness's look for a chip: a sound run is correct, and each fault the
cell can have, planted in the timed path, makes ``correct`` false."""
import dataclasses
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import run  # noqa: E402

sys.path.insert(0, str(harness.ROOT / "src"))

# the published hidden size keeps the logits at the cell's scale (the
# embedding's std 0.02 times sqrt(4096)), and a vocabulary of 16384 keeps
# enough near rivals for a lower precision to pick wrong tokens; depth,
# heads and MLP are cut so that the CPU holds it
SMALL = {"hidden_size": 4096, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "vocab_size": 16384, "num_hidden_layers": 1, "batch_size": 4,
         "max_prompt": 64, "max_new_tokens": 16}
SMALL_MIX = {"rate_per_s": 3.0,
             "prompt_tokens": {"median": 24, "sigma": 0.8, "min": 8,
                               "max": 64},
             "output_tokens": {"median": 10, "sigma": 0.4, "min": 6,
                               "max": 16}}


def _run(control=False, seconds=4.0):
    cell = harness.resolve_cell(harness.load_spec(), "serve.chat")
    cell = dataclasses.replace(cell, config={**cell.config, **SMALL},
                               traffic={**cell.traffic, **SMALL_MIX})
    return run.run_cell("serve.chat", 2**31 + 202, seconds, False,
                        require_tpu=False, control=control, cell=cell)


def _decode_wrapped(monkeypatch, change):
    """Wrap the engine's fused decode step: ``change(outputs, inputs)``
    returns the (tokens, caches, logits) the step hands back."""
    import repro.serve.engine as engine
    orig = engine._make_decode_step

    def make(model):
        fn = orig(model)

        def step(*args):
            return change(fn(*args), args)
        return step
    monkeypatch.setattr(engine, "_make_decode_step", make)


def _token_altered(monkeypatch):
    """Every decoded token is replaced by the next id where produced."""
    _decode_wrapped(monkeypatch, lambda out, args:
                    ((out[0] + 1) % SMALL["vocab_size"],) + out[1:])


def _half_batch(monkeypatch):
    """Half of the batch is left out: each odd row gets the token of the
    row before it."""
    import jax.numpy as jnp

    def half(out, args):
        nxt = out[0]
        even = jnp.arange(nxt.shape[0]) // 2 * 2
        return (nxt[even],) + out[1:]
    _decode_wrapped(monkeypatch, half)


def _one_slot_altered(monkeypatch):
    """The token of one batch slot is replaced by the next id where
    produced; every other slot is sound."""
    def one(out, args):
        nxt = out[0]
        return (nxt.at[1].set((nxt[1] + 1) % SMALL["vocab_size"]),) + out[1:]
    _decode_wrapped(monkeypatch, one)


def _state_unchanged(monkeypatch):
    """The decode step hands back the caches it was given."""
    _decode_wrapped(monkeypatch, lambda out, args:
                    (out[0], args[2], out[2]))


def test_a_sound_run_is_correct_and_reports_the_cell():
    line = _run()
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", [_token_altered, _half_batch,
                                   _state_unchanged, _one_slot_altered])
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = _run()
    assert line["correct"] is False, (fault.__name__, line["checks"])


def test_the_lower_precision_control_is_not_correct():
    line = _run(control=True)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]


def test_the_sample_holds_the_longest_and_one_request_of_every_slot():
    import types
    serve = harness.load_module(harness.system_path("serve"),
                                "chipbench_system")
    reqs = [types.SimpleNamespace(max_new_tokens=n)
            for n in (40, 250, 32, 90, 64, 128, 33, 77)]
    slot_of = {0: 0, 1: 1, 2: 0, 3: 2, 4: 3, 5: 1, 6: 3, 7: 2}
    done = list(range(8))
    pick = serve.sample(done, reqs, slot_of, 2**31 + 5)
    assert pick[0] == 1                          # the longest
    assert sorted(slot_of[i] for i in pick) == [0, 1, 2, 3]
    assert pick == serve.sample(done, reqs, slot_of, 2**31 + 5)
