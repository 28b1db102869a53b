"""The layer scan carries the KV cache and updates it in place.

``transformer.forward_cached`` passes the stacked per-layer K/V (and the
int8 scales under ``kv_quant``) through ``lax.scan`` as its carry: each
layer writes its new entries into its layer of the stack and reads its
attention view back from the same array.  No stacked ``ys`` pool is
rebuilt, and with the state donated the CPU-compiled program copies
neither the pool nor a layer of it (``test_tpu_compile`` holds the TPU,
whose layouts the CPU does not pick, to no copy of the pool).  The write
is also exact: only the appending row's slots change, and the logits
match the contiguous layout and the uncached trainer forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import harness
from repro.launch import hlo_analysis
from repro.models import ModelConfig
from repro.models import kv_cache as kvc
from repro.models.model import LanguageModel

LAYOUTS = ["paged", "contiguous"]
BLOCK = 8


def tiny_cfg(quant=False, window=0):
    return ModelConfig(name="carry", arch_type="dense", num_layers=3,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=96,
                       vocab_size=53, dtype=jnp.float32, kv_quant=quant,
                       sliding_window=window,
                       local_global_ratio=1 if window else 0)


def _make_state(lm, layout, batch=2, max_len=40):
    st, _ = lm.make_state(batch, max_len, paged=layout == "paged",
                          block_size=BLOCK)
    return st


def _pool_copies(hlo_text, stacked_shapes):
    """Copies whose result is a stacked cache array (L, ...), one layer of
    it, or that layer with its unit layer axis."""
    banned = set()
    for shape in map(tuple, stacked_shapes):
        banned |= {shape, shape[1:], (1,) + shape[1:]}
    return hlo_analysis.copies_of(hlo_text, banned)


# ---------------------------------------------------------------------------
# compiled programs: no copy of the pool
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("quant", [False, True], ids=["bf", "int8"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_decode_makes_no_copy_of_the_cache(layout, quant):
    lm = LanguageModel(tiny_cfg(quant))
    params, _ = lm.init(jax.random.PRNGKey(0))
    st = _make_state(lm, layout)
    stacked = [st.layers[n].shape for n in
               (("k", "v", "k_scale", "v_scale") if quant else ("k", "v"))]
    tokens = jnp.zeros((2, 1), jnp.int32)
    text = jax.jit(lm.decode, donate_argnums=(1,)).lower(
        params, st, tokens).compile().as_text()
    assert _pool_copies(text, stacked) == []


def test_fused_cycle_makes_no_copy_of_any_members_cache():
    """The real fused linear program (draft scan, verify, rollback, commit)
    compiled as ``hlo_rules.check_compiled_program`` compiles it."""
    cap = harness.capture_fused_linear()
    states = cap.arg_sds[1]
    assert len(states) == len(cap.chain)
    stacked = [st.layers[n].shape for st in states for n in ("k", "v")]
    text = jax.jit(cap.body, donate_argnums=harness.DONATE_ARGNUMS).lower(
        *cap.arg_sds).compile().as_text()
    assert _pool_copies(text, stacked) == []


# ---------------------------------------------------------------------------
# the write lands exactly where it should
# ---------------------------------------------------------------------------
CASES = [(layout, quant, window) for layout in LAYOUTS
         for quant in (False, True) for window in (0, 4)]


def _case_id(c):
    return f"{c[0]}-{'int8' if c[1] else 'bf'}-w{c[2]}"


def _changed(before, after, layout):
    """Per layer, the (row, slot) or pool-slot entries whose K/V differ."""
    d = np.asarray(before) != np.asarray(after)
    axes = tuple(range(3 if layout == "contiguous" else 2, d.ndim))
    return d.any(axis=axes)


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_one_rows_decode_touches_only_its_own_slots(case):
    layout, quant, window = case
    lm = LanguageModel(tiny_cfg(quant, window))
    params, _ = lm.init(jax.random.PRNGKey(1))
    st = _make_state(lm, layout)
    prompt = jnp.array([[3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 0, 0]],
                       jnp.int32)
    pvalid = jnp.array([[True] * 6, [True] * 4 + [False] * 2])
    _, st = lm.prefill(params, st, prompt, valid=pvalid)
    before = {n: np.asarray(a) for n, a in st.layers.items()}
    slot0 = int(np.asarray(st.write_ptr if layout == "contiguous"
                           else st.write_ptr[0]))
    valid = jnp.array([[True], [False]])
    _, st2 = jax.jit(lm.decode, donate_argnums=(1,))(
        params, st, jnp.array([[21], [22]], jnp.int32), valid=valid)

    for n, a in st2.layers.items():
        changed = _changed(before[n], a, layout)
        for layer in range(changed.shape[0]):
            if layout == "paged":
                phys = int(np.asarray(kvc.physical_slots(
                    st2, jnp.array([[slot0]], jnp.int32)))[0, 0])
                # row 1's blocks: every pool slot row 1 owns is untouched
                assert np.flatnonzero(changed[layer]).tolist() == [phys], n
            else:
                # the contiguous layout appends every row at the shared
                # slot; row 1's entry there is masked off, and nothing else
                # of row 1 moves
                assert np.flatnonzero(changed[layer, 0]).tolist() == [slot0]
                assert not changed[layer, 1, :slot0].any(), n
                assert not changed[layer, 1, slot0 + 1:].any(), n
    if layout == "contiguous":
        assert not bool(np.asarray(st2.mask)[1, slot0])


STEPS = [jnp.array([[14, 15], [16, 17]], jnp.int32),
         jnp.array([[18], [19]], jnp.int32),
         jnp.array([[20], [23]], jnp.int32),
         jnp.array([[24], [25]], jnp.int32)]


def _decode_run(lm, params, layout, sit_out=False):
    """Prefill two rows, then four decode steps (eager, as
    ``test_paged_kv`` runs them); with ``sit_out`` row 1 skips the second.
    Returns each row's valid stream and the logits at each of its
    positions."""
    st = _make_state(lm, layout)
    prompt = jnp.array([[3, 4, 5, 6, 7], [9, 10, 11, 12, 13]], jnp.int32)
    logits, st = lm.prefill(params, st, prompt, logits_mode="all")
    streams = [list(np.asarray(prompt[r])) for r in range(2)]
    out = [list(np.asarray(logits[r])) for r in range(2)]
    for i, toks in enumerate(STEPS):
        valid = np.ones(toks.shape, bool)
        if sit_out and i == 1:
            valid[1] = False
        logits, st = lm.decode(params, st, toks, valid=jnp.asarray(valid))
        for r in range(2):
            for t, v, lg in zip(np.asarray(toks[r]), valid[r],
                                np.asarray(logits[r])):
                if v:
                    streams[r].append(int(t))
                    out[r].append(lg)
    return streams, [np.stack(o) for o in out]


@pytest.mark.parametrize("quant", [False, True], ids=["bf", "int8"])
@pytest.mark.parametrize("window", [0, 4])
def test_paged_logits_equal_contiguous(quant, window):
    """Bit-identical, as ``test_paged_kv`` pins without the carry: both
    layouts attend over the same entries in the same slots."""
    lm = LanguageModel(tiny_cfg(quant, window))
    params, _ = lm.init(jax.random.PRNGKey(2))
    _, paged = _decode_run(lm, params, "paged")
    _, contiguous = _decode_run(lm, params, "contiguous")
    for a, b in zip(paged, contiguous):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("window", [0, 4])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_cached_logits_match_the_trainer(layout, window):
    """Float32, with row 1 sitting out a step: the cached logits at every
    valid position are the uncached trainer forward's on that row's
    stream."""
    lm = LanguageModel(tiny_cfg(window=window))
    params, _ = lm.init(jax.random.PRNGKey(2))
    streams, cached = _decode_run(lm, params, layout, sit_out=True)
    for stream, lg in zip(streams, cached):
        ref = lm.train_logits(params, jnp.array([stream], jnp.int32),
                              remat=False)[0]
        np.testing.assert_allclose(lg, np.asarray(ref), atol=1e-5, rtol=0)
