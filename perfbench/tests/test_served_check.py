"""The served-path check as a family's own (ISSUE 39): who decides which
statistics a configuration gets, what the sample holds, how a family's own
compared numbers are merged and judged, and the rehearsal family
(tests/families/blockfill.py) through the same calls serve_child makes, with
its faults planted.  Toy width, CPU."""

import json

import numpy as np
import pytest

from perfbench import run
from perfbench.families import dense_gqa
from perfbench.lib import checks, reference, serve_child, spec, weights

SEED = 2**31 + 39
ACCEPTED = {"dense_gqa": "serve-decode", "latent_moe": "serve-moe-mla-decode",
            "swa_moe": "serve-moe-swa-longdoc",
            "conv_moe": "serve-moe-conv-chat", "moe_switch": None}


def _config(family):
    cell = ACCEPTED[family]
    return spec.cell(cell)[1] if cell else spec.family({"family": family}).TOY


# ------------------------------------------------------- who gives the statistics
@pytest.mark.parametrize("family", sorted(ACCEPTED))
def test_a_family_without_its_own_gets_the_default_itself(family):
    config = _config(family)
    assert spec.family(config).__name__.endswith(family)
    assert reference.served_stats_for(config) is \
        reference.generated_logit_stats


def test_a_family_with_its_own_gets_that():
    fam = spec.family({"family": "blockfill"})
    assert reference.served_stats_for(fam.TOY) is fam.served_stats


@pytest.mark.parametrize("tokens_of,quant", [("served", None),
                                             ("quant", "int8")])
def test_the_default_is_called_with_the_arguments_it_got_before(
        monkeypatch, tokens_of, quant):
    """serve_child's sound pass and its control pass, as they were written
    inline before: (config, seed, seqs, spans as tuples, tokens_of, quant)."""
    calls = []

    def default(*a, **k):
        calls.append((a, k))
        return {"gap": [0.0, 0.2], "flip": [False, True]}
    monkeypatch.setattr(reference, "generated_logit_stats", default)
    config = spec.cell("serve-decode")[1]
    sample = {"seqs": [[5, 6, 7, 0]], "spans": [[0, 2]],
              "done": [{"done": True, "tokens": [6, 7]}], "part_n": [[1, 1]]}
    stats, numbers = serve_child.served_check(config, SEED, sample, tokens_of,
                                              quant)
    assert calls == [((config, SEED, [[5, 6, 7, 0]], [(0, 2)], tokens_of),
                      {"quant": quant})]
    assert numbers == checks.serve_numbers(stats) == {
        "served_gap_share": 0.5, "served_gap_max": 0.2}


# ------------------------------------------------------------------- the sample
def test_the_sample_is_the_golden_one_with_the_stream_beside_it():
    reqs = [{"tokens": [11, 12, 13]}, {"tokens": [21]},
            {"tokens": [31, 32, 33, 34, 35]}]
    done = [{"done": True, "tokens": [14, 15], "finish_reason": "length",
             "steps": [0, 1]}, None,
            {"done": True, "tokens": [36, 37, 38], "timing": {"queue": 0.1}}]
    records = [{"tokens": [14, 15], "part_n": [1, 1], "done": done[0]},
               {"tokens": [], "part_n": [], "done": None},
               {"tokens": [36, 37, 38], "part_n": [2, 1], "done": done[2]}]
    sample = run.build_sample([2, 0], reqs, records, 7)
    # what run_serve's own lines wrote before this function held them
    assert sample["seqs"] == [[31, 32, 33, 34, 35, 36, 37],
                              [11, 12, 13, 14, 15, 0, 0]]
    assert sample["spans"] == [[4, 3], [2, 2]]
    assert sample["done"] == [done[2], done[0]]
    assert sample["part_n"] == [[2, 1], [1, 1]]
    assert set(sample) == {"seqs", "spans", "done", "part_n"}
    assert json.loads(json.dumps(sample)) == sample
    empty = run.build_sample([], reqs, records, 7)
    assert empty == {"seqs": [], "spans": [], "done": [], "part_n": []}


# ------------------------------------------------------ a family's own numbers
def test_a_familys_number_is_merged_and_judged(monkeypatch):
    fam = spec.family({"family": "blockfill"})
    monkeypatch.setattr(fam, "served_stats", lambda *a, **k: {
        "gap": [0.0], "flip": [False], "numbers": {"own_share": 0.25}})
    _, numbers = serve_child.served_check(fam.TOY, SEED, {}, "served")
    assert numbers == {"served_gap_share": 0.0, "served_gap_max": 0.0,
                       "own_share": 0.25}
    limits = {"served_gap_share": 0.1, "served_gap_max": 3.5}
    assert checks.judge(numbers, dict(limits, own_share=0.3))[1]
    rows, ok = checks.judge(numbers, dict(limits, own_share=0.2))
    assert not ok and [r[0] for r in rows if not r[3]] == ["own_share"]
    with pytest.raises(KeyError):        # a number without a limit
        checks.judge(numbers, limits)


@pytest.mark.parametrize("name", checks.HARNESS_NUMBERS)
def test_a_familys_number_named_as_one_of_the_harness_is_refused(name):
    assert checks.family_numbers({"gap": [0.0], "flip": [False]}) == {}
    with pytest.raises(ValueError, match=name):
        checks.family_numbers({"numbers": {name: 0.0, "own": 1.0}})


# --------------------------------------------------------- the rehearsal family
FAM = spec.family({"family": "blockfill"})
TOY = FAM.TOY


def _logits(config, seed, causal=False):
    """The tests' "program": the family's equations on weights made as the
    harness makes a program's (``causal``: under dense_gqa's mask, fault A)."""
    import jax
    import jax.numpy as jnp
    params = weights.flat(jax.jit(
        lambda key: weights.make(config, key, jnp.float32))(
            weights.seed_key(seed)))
    step = dense_gqa.layer if causal else FAM.layer

    @jax.jit
    def logits(row):
        x = FAM.embed(params, row[None], config)
        for i in range(config["num_hidden_layers"]):
            pre = f"layers.{i}."
            x = step("dense", {k[len(pre):]: v for k, v in params.items()
                               if k.startswith(pre)}, x, config,
                     reference.plain_mm)
        return FAM.head(params, x, config, reference.plain_mm)[0]
    return logits


def _generate(logits, config, prompt, n, whole_block_at_once=False):
    """(tokens, steps) of n positions after ``prompt``, block by block as
    blockfill.py sets out (``whole_block_at_once``: fault B, the threshold
    ignored, the steps reported as they were)."""
    Bk, M = config["block_length"], FAM.mask_id(config)
    start, end = len(prompt), len(prompt) + n
    row = np.full(-(-end // Bk) * Bk, M, np.int32)
    row[:start] = prompt
    step = {}
    for P in range(start - start % Bk, end, Bk):
        masked = [j for j in range(P, P + Bk) if start <= j < end]
        s = 0
        while masked:
            tok, conf = (np.asarray(a) for a in FAM.candidates(
                logits(row)[P:P + Bk], config))
            fill = {j for j in masked if whole_block_at_once
                    or conf[j - P] >= config["unmask_threshold"]}
            fill.add(max(masked, key=lambda j: conf[j - P]))
            for j in fill:
                row[j], step[j] = tok[j - P], s
            masked = [j for j in masked if j not in fill]
            s += 1
    return row[start:end].tolist(), [step[j] for j in range(start, end)]


def _sample(served, T=64):
    """[(prompt, tokens, steps)] as run.build_sample would hand them on."""
    reqs = [{"tokens": p} for p, _, _ in served]
    records = [{"tokens": o, "part_n": [len(o)],
                "done": {"done": True, "tokens": o, "steps": s}}
               for _, o, s in served]
    return run.build_sample(range(len(served)), reqs, records, T)


PROMPTS = [np.random.default_rng(5 + i).integers(0, 255, n).tolist()
           for i, n in enumerate((18, 13, 24))]
N_NEW = (22, 16, 17)        # ends inside a block, on a block's edge, inside


def _limits():
    return dict(spec.cell("serve-decode")[2]["check"]["limits"],
                **FAM.OWN_LIMITS)


def _judged(served, tokens_of="served", quant=None, config=TOY, seed=SEED):
    stats, numbers = serve_child.served_check(config, seed, _sample(served),
                                              tokens_of, quant)
    assert len(stats["gap"]) == len(stats["flip"]) == sum(
        len(o) for _, o, _ in served)
    limits = _limits()
    rows, ok = checks.judge(numbers, {k: limits[k] for k in numbers})
    return numbers, {r[0] for r in rows if not r[3]}, ok


@pytest.fixture(scope="module")
def sound():
    logits = _logits(TOY, SEED)
    return [(p,) + _generate(logits, TOY, p, n)
            for p, n in zip(PROMPTS, N_NEW)]


def test_the_rule_fills_one_position_in_some_passes_and_several_in_others(
        sound):
    steps = [s for _, _, ss in sound for s in ss]
    assert len(steps) == sum(N_NEW) and min(steps) == 0
    assert 1 < max(steps) <= TOY["block_length"] - 1
    assert steps.count(0) > len(steps) / TOY["block_length"]
    assert all(FAM.mask_id(TOY) not in o for _, o, _ in sound)


def test_the_sound_record_passes(sound):
    numbers, failed, ok = _judged(sound)
    assert ok, numbers
    assert numbers["early_unmask_share"] == 0.0
    assert numbers["served_gap_max"] < 1e-3


def test_the_gaps_come_by_request_then_by_position(sound):
    """One token altered: the one gap that is wide sits where serve_child's
    ``worst_gaps`` listing will say it does."""
    p, o, s = sound[1]
    wrong = list(o)
    wrong[5] = (wrong[5] + 1) % FAM.mask_id(TOY)
    stats, _ = serve_child.served_check(
        TOY, SEED, _sample([sound[0], (p, wrong, s)]), "served")
    worst = int(np.argmax(stats["gap"]))
    assert worst == len(sound[0][1]) + 5 and stats["flip"][worst]


def test_fault_a_tokens_under_the_causal_mask_fail_by_the_gaps():
    logits = _logits(TOY, SEED, causal=True)
    served = [(p,) + _generate(logits, TOY, p, n)
              for p, n in zip(PROMPTS, N_NEW)]
    numbers, failed, ok = _judged(served)
    assert not ok and "served_gap_share" in failed, numbers


def test_fault_b_a_block_filled_in_its_first_pass_passes_the_gaps_and_fails_by_its_own_number(
        sound):
    logits = _logits(TOY, SEED)
    served = [(p,) + _generate(logits, TOY, p, n, whole_block_at_once=True)
              for p, n in zip(PROMPTS, N_NEW)]
    assert all(set(s) == {0} for _, _, s in served)
    assert [o for _, o, _ in served] != [o for _, o, _ in sound]
    numbers, failed, ok = _judged(served)
    assert failed == {"early_unmask_share"}, numbers
    assert numbers["served_gap_max"] < 1e-3
    assert numbers["early_unmask_share"] > 5 * FAM.OWN_LIMITS[
        "early_unmask_share"]


def test_a_served_mask_id_fails(sound):
    p, o, s = sound[0]
    numbers, failed, ok = _judged([(p, [FAM.mask_id(TOY)] + o[1:], s)])
    assert not ok and numbers["served_gap_max"] == float("inf")


def test_its_own_number_without_a_limit_is_an_error(sound):
    _, numbers = serve_child.served_check(TOY, SEED, _sample(sound), "served")
    assert "early_unmask_share" in numbers
    with pytest.raises(KeyError, match="early_unmask_share"):
        checks.judge(numbers, spec.cell("serve-decode")[2]["check"]["limits"])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_int8_control_comes_out_not_correct(seed):
    """The control at a size a test run can hold (hidden 128, 32 layers,
    vocabulary 8192), through ``tokens_of="quant"``: in the states that a
    record names, the token the int8 forward puts first."""
    config = dict(TOY, hidden_size=128, intermediate_size=512,
                  num_hidden_layers=32, vocab_size=8192)
    rng = np.random.default_rng(seed)
    served = [(rng.integers(0, 8191, 32).tolist(),
               rng.integers(0, 8191, 32).tolist(),
               [int(s) for b in range(8) for s in rng.permutation(4) // 2])
              for _ in range(3)]
    numbers, failed, ok = _judged(served, "quant", "int8", config, seed)
    assert not ok and "served_gap_share" in failed, numbers
