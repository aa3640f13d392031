"""The train step chooses what its backward keeps from the compiled step's
memory against the device's (``tony_tpu.remat``, ISSUE 30).

The chooser is driven against a faked compiler and device: no rung is
compiled here, every "compile" is a row of a table. The names, the policy
and the numerics are checked on the tiny decoders, on the CPU, where the
real step keeps nothing (the backend reports no limit).
"""

import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax._src.ad_checkpoint import saved_residuals

from tony_tpu import profiler, remat, train
from tony_tpu.models import get_model

GiB = 1 << 30
# The dense decoder's rungs: the ladder's without the name it never meets
# (``sel``, a learned selection's), which leaves its last rung the floor.
FULL, NO_WO, MLP, QKV, _ = (tuple(n for n in rung if n != "sel")
                            for rung in remat.LADDER)
RUNGS = (FULL, NO_WO, MLP, QKV, remat.FLOOR)
FENCED = ("prevent_cse",)    # the fake's key of the floor with prevent_cse;
#                              a fenced rung's is ``rung + FENCED``
ALL_NAMES = set(FULL) | {"flash_out", "flash_lse"}


class FakeCompiler:
    """``build`` for a ChosenStep: a step "traces" to its rung and
    "compiles" into ``table[rung]`` bytes, is refused where the table says
    None, and fails with the error the table holds. The traced model has
    the names ``model_names``; a rung is known here, in the table and in
    the three logs, by the names of it that the model has (the program it
    is)."""

    def __init__(self, table, model_names=ALL_NAMES, blocks=1):
        self.table, self.names, self.blocks = table, model_names, blocks
        self.traced, self.lowered, self.compiled = [], [], []

    def __call__(self, saved):
        outer = self
        rung = tuple(n for n in saved.names
                     if self.blocks and n in self.names)
        if saved.prevent_cse:
            rung += FENCED

        class Traced:
            def lower(self):
                outer.lowered.append(rung)
                return self

            def compile(self):
                outer.compiled.append(rung)
                total = outer.table[rung]
                if isinstance(total, Exception):
                    raise total
                if total is None:
                    raise jax.errors.JaxRuntimeError(
                        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent "
                        "error. Ran out of memory in memory space hbm.")
                return FakeCompiled(total)

        class Step:
            def trace(self, state, batch):
                outer.traced.append(rung)
                saved.met |= set(outer.names)
                saved.blocks += outer.blocks
                return Traced()

            def __call__(self, state, batch):
                return ("ran", rung)

        return Step()


class FakeCompiled:
    def __init__(self, total):
        self.total = total

    def memory_analysis(self):
        class M:
            argument_size_in_bytes = 8 * GiB
            output_size_in_bytes = 8 * GiB
            alias_size_in_bytes = 8 * GiB
            temp_size_in_bytes = self.total - 8 * GiB
        return M()


class FakeDevice:
    device_kind = "TPU v5 lite"

    class client:
        platform_version = "fake libtpu 0.0.34"

    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return None if self.limit is None else {"bytes_limit": self.limit}


STATE = {"w": np.zeros((4, 8), np.float32)}
BATCH = {"x": np.zeros((2, 16), np.int32)}
LIMIT = int(15.75 * GiB)
# The Mistral cell's compiles for a described v5e (ISSUE 30's table).
MISTRAL = {FULL: int(15.321 * GiB), NO_WO: int(14.695 * GiB),
           MLP: int(14.445 * GiB), QKV: int(13.821 * GiB),
           remat.FLOOR: int(13.570 * GiB)}
# The Olmo Hybrid cell's (ISSUE 41's): four unrolled layers whose second
# forwards XLA merges with the first, every rung refused alike; fenced,
# the rungs read these, but the top one, which each test sets.
FENCED_WALK = tuple(r + FENCED for r in RUNGS[::-1])   # the floor upwards
OLMO = {**{r: None for r in RUNGS},
        FENCED: int(12.78 * GiB), QKV + FENCED: int(13.31 * GiB),
        MLP + FENCED: int(14.11 * GiB), NO_WO + FENCED: int(14.63 * GiB)}


@pytest.fixture
def memo_dir(tmp_path):
    """The memo under ``tmp_path``; the timeline starts and ends empty."""
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    profiler.reset_timeline()
    profiler.reset_records("remat")
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_enable_compilation_cache", was[1])
    profiler.reset_timeline()
    profiler.reset_records("remat")


@pytest.fixture
def chooser(monkeypatch, memo_dir):
    """``choose(table, ...)`` -> (the chosen rung, the fake compiler)."""
    def choose(table, limit=LIMIT, state=STATE, batch=BATCH, **fake):
        monkeypatch.setattr(remat, "_device_of",
                            lambda _state: FakeDevice(limit))
        compiler = FakeCompiler(table, **fake)
        step = remat.ChosenStep(compiler)
        ran, names = step(state, batch)
        assert ran == "ran"
        assert step(state, batch) == ("ran", names)     # settled
        return names, compiler
    return choose


def test_takes_the_richest_set_that_leaves_the_margin(chooser):
    names, compiler = chooser(MISTRAL)
    assert names == NO_WO
    # 15.321 GiB compiles but leaves 0.43 GiB: tried and passed over.
    assert compiler.compiled == [FULL, NO_WO]
    assert MISTRAL[NO_WO] + remat.MARGIN <= LIMIT < MISTRAL[FULL] \
        + remat.MARGIN
    c = profiler.counters()
    assert {n for n in ALL_NAMES if f"remat:saved.{n}" in c} == set(NO_WO)
    assert c["remat:step_bytes"] == MISTRAL[NO_WO]
    assert c["remat:bytes_limit"] == LIMIT
    assert c["remat:step_bytes"] <= c["remat:bytes_limit"] - remat.MARGIN
    assert (c["remat:rungs_tried"], c["remat:rungs_refused"],
            c["remat:from_memo"]) == (2, 0, 0)
    plan = profiler.report("remat")["train_step"]
    assert [r["bytes"] for r in plan["rungs"]] == [MISTRAL[FULL],
                                                   MISTRAL[NO_WO]]


def test_steps_down_on_a_refused_compile(chooser):
    names, compiler = chooser({**MISTRAL, FULL: None, NO_WO: None})
    assert names == MLP
    assert compiler.compiled == [FULL, NO_WO, MLP]
    c = profiler.counters()
    assert (c["remat:rungs_tried"], c["remat:rungs_refused"]) == (3, 2)


def test_another_compile_error_is_not_a_step_down(chooser):
    broken = jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile")
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        chooser({**MISTRAL, FULL: broken})


def test_floor_is_taken_whatever_the_margin_says(chooser):
    tight = {r: LIMIT - 1 for r in RUNGS}
    names, compiler = chooser(tight)
    assert names == remat.FLOOR
    assert compiler.compiled == list(RUNGS)
    assert profiler.counters()["remat:step_bytes"] == LIMIT - 1


def _rung_spans():
    return [sp["attrs"] for sp in profiler.timeline()["spans"]
            if sp["name"] == "tony:remat_rung"]


@pytest.mark.parametrize("top, want", [
    (None, NO_WO), (15.2, NO_WO), (14.9, FULL)],
    ids=["top-refused", "top-over-the-margin", "top-fits"])
def test_a_refused_floor_walks_the_rungs_again_under_the_fence(
        chooser, top, want):
    """Unrolled layers whose second forward XLA merges with the first can
    hold more than the chip has, whatever the rung keeps (the 16k Olmo
    Hybrid step): once the unfenced floor is refused the rungs are
    candidates under ``prevent_cse`` by the unfenced walk's rule — the
    richest that compiles and leaves the margin — walked from the fenced
    floor upwards. The memo remembers set and fence; a warm start builds
    that one program and tries no rung."""
    table = {**OLMO, FULL + FENCED: top and int(top * GiB)}
    names, compiler = chooser(table)
    assert names == want + FENCED
    assert compiler.compiled == list(RUNGS) + list(FENCED_WALK)
    assert compiler.traced == compiler.compiled     # one trace a rung
    c = profiler.counters()
    assert (c["remat:rungs_tried"], c["remat:rungs_refused"],
            c["remat:rungs_fenced"], c["remat:prevent_cse"]) == (
                10, 5 + (top is None), 5, 1)
    assert {n for n in ALL_NAMES if f"remat:saved.{n}" in c} == set(want)
    assert c["remat:step_bytes"] == table[want + FENCED]
    assert c["remat:step_bytes"] + remat.MARGIN <= c["remat:bytes_limit"]
    rungs = profiler.report("remat")["train_step"]["rungs"]
    assert [r["prevent_cse"] for r in rungs] == [False] * 5 + [True] * 5
    assert _rung_spans() == [{**r, "saved": ",".join(r["saved"])}
                             for r in rungs]     # a span a rung, in order
    profiler.reset_timeline()
    names, compiler = chooser(table)
    assert names == want + FENCED
    assert compiler.traced == [] and compiler.compiled == []
    assert _rung_spans() == []
    c = profiler.counters()
    assert (c["remat:from_memo"], c["remat:prevent_cse"],
            c["remat:rungs_tried"], c["remat:rungs_refused"],
            c["remat:rungs_fenced"]) == (1, 1, 0, 0, 0)
    assert {n for n in ALL_NAMES if f"remat:saved.{n}" in c} == set(want)
    assert c["remat:step_bytes"] == table[want + FENCED]


@pytest.mark.parametrize("fenced, want, walked", [
    ({QKV + FENCED: None}, remat.FLOOR, 2),
    ({QKV + FENCED: int(15.2 * GiB)}, remat.FLOOR, 2),
    ({QKV + FENCED: int(14.9 * GiB), MLP + FENCED: None}, QKV, 3),
    ({QKV + FENCED: None, MLP + FENCED: int(14.0 * GiB)}, remat.FLOOR, 2)],
    ids=["refused", "over-the-margin", "the-next-refused",
         "a-richer-one-would-fit"])
def test_a_refused_floor_is_tried_again_with_its_second_forward_fenced(
        chooser, fenced, want, walked):
    """The fenced floor is compiled first and the walk above it stops at
    the first rung that does not fit: ``RESOURCE_EXHAUSTED`` on a fenced
    rung is a step down, not the step's error, so a step with no room
    above its fenced floor (the 32k Kimi Linear step, 14.76 GiB) pays one
    more compile, not one a rung. The rung before is the step — the
    fenced floor where the poorest rung already fails, whatever a richer
    one would read; the memo remembers which, and a warm start builds
    that one."""
    table = {**{r: None for r in RUNGS}, FENCED: int(14.76 * GiB), **fenced}
    names, compiler = chooser(table)
    assert names == want + FENCED
    assert compiler.compiled == list(RUNGS) + list(FENCED_WALK[:walked])
    c = profiler.counters()
    assert (c["remat:rungs_tried"], c["remat:rungs_fenced"],
            c["remat:prevent_cse"]) == (5 + walked, walked, 1)
    assert c["remat:rungs_refused"] == sum(
        table[r] is None for r in compiler.compiled)
    assert c["remat:step_bytes"] == table[want + FENCED]
    assert not [n for n in ALL_NAMES if f"remat:saved.{n}" in c
                and n not in want]
    profiler.reset_timeline()
    names, compiler = chooser(table)
    assert names == want + FENCED and compiler.compiled == []
    c = profiler.counters()
    assert (c["remat:from_memo"], c["remat:prevent_cse"]) == (1, 1)


def test_a_model_with_nothing_to_keep_has_no_fenced_rung_to_try(chooser):
    """The fenced floor is then the whole second walk (the layer-kind
    decoder's mixers before ISSUE 38 named their values)."""
    table = {remat.FLOOR: None, FENCED: int(14.76 * GiB)}
    names, compiler = chooser(table, model_names={"flash_out"})
    assert names == FENCED
    assert compiler.compiled == [remat.FLOOR, FENCED]
    c = profiler.counters()
    assert (c["remat:rungs_tried"], c["remat:rungs_refused"],
            c["remat:rungs_fenced"]) == (2, 1, 1)


@pytest.mark.parametrize("table", [
    {**{r: LIMIT - 1 for r in RUNGS}, **{r: GiB for r in FENCED_WALK}},
    {**MISTRAL, **{r: GiB for r in FENCED_WALK}},
    {**{r: None for r in RUNGS[:-1]}, remat.FLOOR: LIMIT - 1,
     **{r: GiB for r in FENCED_WALK}}],
    ids=["floor-over-the-margin", "a-rung-fits", "rungs-refused-floor-not"])
def test_a_floor_that_compiles_is_never_fenced(chooser, table):
    """The second walk is entered on one observation, the unfenced floor's
    refusal: a step whose unfenced walk ends on a rung or on its floor
    (the Mistral, phi, Keye and ZAYA1 cells) tries nothing under the
    fence, however little the fenced programs would hold."""
    names, compiler = chooser(table)
    assert FENCED[0] not in names
    assert not [r for r in compiler.traced + compiler.compiled
                if FENCED[0] in r]
    c = profiler.counters()
    assert "remat:prevent_cse" not in c and c["remat:rungs_fenced"] == 0
    assert not any(r["prevent_cse"] for r in profiler.report("remat")[
        "train_step"]["rungs"])


def test_a_refused_fenced_floor_is_the_steps_error(chooser):
    """Fenced rungs that would fit do not save it: the walk starts from
    the floor, and no program is poorer."""
    table = {**{r: None for r in RUNGS}, **{r: GiB for r in FENCED_WALK},
             FENCED: None}
    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="RESOURCE_EXHAUSTED"):
        chooser(table)


def test_another_compile_error_under_the_fence_is_not_a_step_down(chooser):
    broken = jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile")
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        chooser({**OLMO, MLP + FENCED: broken})


def test_no_limit_reported_keeps_nothing_and_compiles_nothing(chooser):
    names, compiler = chooser(MISTRAL, limit=None)
    assert names == remat.FLOOR
    assert compiler.traced == [] and compiler.compiled == []
    assert not [k for k in profiler.counters() if k.startswith("remat:")]


def test_second_start_reads_the_memo_and_compiles_once(chooser):
    chooser({**MISTRAL, FULL: None})
    profiler.reset_timeline()
    names, compiler = chooser({**MISTRAL, FULL: None})
    assert names == NO_WO
    # Nothing probed, the refused compile not repeated: the one program a
    # warm start builds is the chosen step's own first call.
    assert compiler.traced == [] and compiler.compiled == []
    c = profiler.counters()
    assert (c["remat:from_memo"], c["remat:rungs_tried"],
            c["remat:rungs_refused"]) == (1, 0, 0)
    assert c["remat:step_bytes"] == MISTRAL[NO_WO]
    assert "remat:saved.gate" in c and "remat:saved.wo" not in c


@pytest.mark.parametrize("other", ["batch", "state", "limit"])
def test_memo_of_another_shape_or_limit_is_ignored(chooser, other):
    chooser(MISTRAL)
    kwargs = {"batch": {"batch": {"x": np.zeros((4, 16), np.int32)}},
              "state": {"state": {"w": np.zeros((4, 16), np.float32)}},
              "limit": {"limit": LIMIT + GiB}}[other]
    names, compiler = chooser(MISTRAL, **kwargs)
    assert compiler.compiled[0] == FULL             # the ladder again
    assert names == (FULL if other == "limit" else NO_WO)


def test_names_the_model_lacks_do_not_make_rungs(chooser):
    """A decoder without gate/up (sparse experts) has two rungs and the
    floor."""
    table = {("q", "k", "v", "wo"): None, QKV: 14 * GiB,
             remat.FLOOR: 13 * GiB, FULL: 99 * GiB}
    names, compiler = chooser(table, model_names={"q", "k", "v", "wo"})
    assert names == QKV
    assert compiler.compiled == [("q", "k", "v", "wo"), QKV]


@pytest.mark.parametrize("sel_bytes, want", [
    (14.92, ("sel",)), (15.2, remat.FLOOR)], ids=["sel-fits", "sel-does-not"])
def test_a_learned_selection_is_the_last_residual_to_go(
        chooser, sel_bytes, want):
    """A decoder with an indexer and sparse experts (no gate/up): the
    selection's bits are kept or recomputed by the compiled step's bytes,
    as every other residual is, after everything richer was refused."""
    names = {"sel", "q", "k", "v", "wo"}
    table = {("sel", "q", "k", "v", "wo"): None, ("sel", "q", "k", "v"): None,
             ("sel",): int(sel_bytes * GiB), remat.FLOOR: int(14.7 * GiB)}
    got, compiler = chooser(table, model_names=names)
    assert got == want
    assert compiler.compiled == list(table)[:3 + (want == remat.FLOOR)]
    # one trace a rung: the first candidate is its own effective rung
    assert compiler.traced[:2] == list(table)[:2]


@pytest.mark.parametrize("in_step", [True, False], ids=["step", "no-step"])
def test_a_name_kept_always_is_on_the_steps_timeline_and_on_no_rung(in_step):
    """The indexer's kept gradients (ISSUE 37): the model hands the name to
    ``block``, whose policy keeps it beside the step's set; the set, what
    ``kept()`` says and the ladder do not change; a step's timeline says
    so, ``model.init``'s does not."""
    import contextlib

    import flax.linen as nn
    from jax._src.ad_checkpoint import name_p

    policies, real = [], nn.remat
    profiler.reset_timeline()
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(nn, "remat", lambda cls, **k: (
                policies.append(k["policy"]), real(cls, **k))[1])
            with remat.Saved(("sel",)) if in_step \
                    else contextlib.nullcontext() as saved:
                remat.block(nn.Dense, always=("index_grad",))
                assert remat.kept() == ()                   # nothing met yet
                remat.name(jnp.zeros(()), "index_grad")
                assert remat.kept() == ()                   # not of the set
        c = profiler.counters()
    finally:
        profiler.reset_timeline()
    (policy,) = policies
    assert {n for n in ("sel", "q", "index_grad") if policy(name_p, name=n)} \
        == ({"sel", "index_grad"} if in_step else {"index_grad"})
    assert ("remat:saved.index_grad" in c) == in_step
    if in_step:
        assert saved.names == ("sel",) and saved.blocks == 1
        assert saved.effective(remat.LADDER[0]) == ()
    assert not any("index_grad" in rung for rung in remat.LADDER)


@pytest.mark.parametrize("fake", [
    {"model_names": {"flash_out", "flash_lse"}}, {"blocks": 0}],
    ids=["none-of-the-names", "remat-off"])
def test_a_model_with_nothing_to_keep_gets_the_floor(chooser, fake):
    """The layer-kind decoder names none of the ladder's values; a model
    with ``remat=False`` wraps no block. One compile, for the bytes."""
    names, compiler = chooser({remat.FLOOR: 13 * GiB}, **fake)
    assert names == remat.FLOOR
    # The first trace tells, and is the floor's program: traced once.
    assert compiler.traced == [remat.FLOOR]
    assert compiler.lowered == compiler.compiled == [remat.FLOOR]
    c = profiler.counters()
    assert (c["remat:rungs_tried"], c["remat:step_bytes"]) == (1, 13 * GiB)


# --------------------------------------------------------------------------
# The real models: names, policy, numerics.

def _loss_fn(model_name, rung, prevent_cse=False, **kw):
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    model = get_model(model_name, remat=True, **kw)
    state = train.create_train_state(model, optax.adam(1e-3), tokens,
                                     jax.random.PRNGKey(1))
    saved = remat.Saved(rung, prevent_cse)

    def loss(params):
        with saved:
            out = model.apply({"params": params}, tokens, targets=tokens) \
                if kw.get("xent_chunk") else train.next_token_loss(
                    model.apply({"params": params}, tokens), tokens)
        return out
    return loss, state.params, saved


def _kept_shapes(rung, fenced=False):
    loss, params, saved = _loss_fn("llama-tiny", rung, fenced,
                                   scan_layers=False)
    kept = saved_residuals(loss, params)
    assert saved.met == set(FULL) and saved.blocks
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert ("prevent_cse=True" in text, "prevent_cse=False" in text) == (
        fenced, not fenced)
    named = sum(bool(re.search(r"remat\.py:\d+:\d+ \(name\)", why))
                for _, why in kept)
    return collections.Counter(aval.shape for aval, _ in kept), named


@pytest.mark.parametrize("rung, fenced", [
    *((rung, False) for rung in RUNGS[:-1]), (NO_WO, True)],
    ids=lambda v: "+".join(v) if isinstance(v, tuple) else
    "fenced" if v else "merged")
def test_saved_residuals_are_exactly_the_named_values(rung, fenced):
    """What survives the forward beside what the floor keeps (each block's
    input): one value of each named width a layer, nothing else. (jax
    keeps ``silu(gate)`` in ``gate``'s place: the same bytes.) A fenced
    rung keeps what the rung keeps, behind its layers' barriers."""
    widths = {"q": 64, "k": 32, "v": 32, "wo": 64, "gate": 128, "up": 128}
    floor, floor_named = _kept_shapes(remat.FLOOR, fenced)
    kept, named = _kept_shapes(rung, fenced)
    assert floor_named == 0 and (2, 16, 128) not in floor
    assert kept - floor == collections.Counter(
        2 * [(2, 16, widths[n]) for n in rung])            # two layers
    assert not floor - kept
    assert named == 2 * len(set(rung) - {"gate"})


@pytest.mark.parametrize("model_name,kw", [
    ("llama-tiny", {}), ("llama-tiny", {"scan_layers": False}),
    ("hybrid-tiny", {"xent_chunk": 8})],
    ids=["llama-tiny-scan", "llama-tiny-unrolled", "hybrid-tiny"])
def test_every_rung_gives_the_floors_loss_and_gradients(model_name, kw):
    want = None
    for rung in (remat.FLOOR,) + remat.LADDER:
        loss, params, _ = _loss_fn(model_name, rung, **kw)
        got = jax.jit(jax.value_and_grad(loss))(params)
        if want is None:
            want = got
            continue
        assert float(got[0]) == float(want[0]), rung
        for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7, err_msg=rung)


@pytest.mark.parametrize("model_name,kw,has", [
    ("llama-tiny", {}, FULL), ("hybrid-tiny", {"xent_chunk": 8}, ())],
    ids=["llama-tiny", "hybrid-tiny"])
def test_a_name_the_model_lacks_leaves_its_program_alone(model_name, kw, has):
    """The ladder's first candidate is traced with every name and run, if
    chosen, without a second trace: it has to be the program a warm start
    builds from the memo's names (the ones the model has; none at all for
    the layer-kind decoder, whose floor has no policy)."""
    texts = []
    for rung in (remat.LADDER[0], has):
        loss, params, saved = _loss_fn(model_name, rung, **kw)
        texts.append(jax.jit(jax.value_and_grad(loss)).lower(
            params).as_text())
        assert saved.effective(remat.LADDER[0]) == has
    assert texts[0] == texts[1]


@pytest.mark.parametrize("model_name, kw, always", [
    ("llama-tiny", {}, ()), ("hybrid-tiny", {"xent_chunk": 8}, ()),
    ("zaya-tiny", {"xent_chunk": 8}, ()),
    ("keye-tiny", {"xent_chunk": 8}, ("index_grad",))])
def test_only_a_model_with_an_indexer_gets_one_more_name(
        model_name, kw, always, monkeypatch):
    """What ``block`` hands ``nn.remat``: at the floor no policy at all for
    a model without an indexer (the program it built before ISSUE 37) and
    the one name for a model with one; on a rung the rung's names, and that
    name beside them."""
    import flax.linen as nn
    from jax._src.ad_checkpoint import name_p

    policies, real = [], nn.remat
    monkeypatch.setattr(nn, "remat", lambda cls, **k: (
        policies.append(k["policy"]), real(cls, **k))[1])
    every = set(remat.LADDER[0]) | {"index_grad", "flash_out"}
    for rung in (remat.FLOOR, ("sel", "q")):
        loss, params, saved = _loss_fn(model_name, rung, **kw)
        del policies[:]                 # model.init's: no step around it
        jax.eval_shape(loss, params)
        assert policies
        for policy in policies:
            if not rung and not always:
                assert policy is None
                continue
            assert {n for n in every if policy(name_p, name=n)} == {
                *rung, *always}


@pytest.mark.parametrize("model_name, kw", [
    ("llama-tiny", {"remat": True}), ("kimi-linear-tiny", {"remat": True})],
    ids=["scanned", "unrolled"])
def test_only_a_fenced_step_puts_barriers_around_its_layers(model_name, kw):
    """``Saved(prevent_cse=True)`` is heard by :func:`remat.block` in both
    decoders, at the floor and on a rung, whose layers keep the rung's
    names behind the barriers; every other step, and a trace outside a
    step, is the program it was (``prevent_cse=False`` on every layer's
    ``checkpoint``; the barriers themselves are put in when the step is
    lowered)."""
    tokens = jnp.zeros((1, 64), jnp.int32)
    model = get_model(model_name, **kw)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    def barriers(saved):
        def loss(p):
            out = model.apply({"params": p}, tokens,
                              mutable=["losses", "stats"])[0]
            return jnp.sum(out.astype(jnp.float32))
        with saved or contextlib.nullcontext():
            text = str(jax.make_jaxpr(jax.grad(loss))(params))
        return (text.count("prevent_cse=True"),
                text.count("prevent_cse=False"),
                text.count("policy=<function save_only_these_names"))

    # (an expert layer's per-chunk ``jax.checkpoint`` is fenced always)
    fenced, merged, policies = barriers(None)
    assert merged > 0 and policies == 0
    assert barriers(remat.Saved()) == (fenced, merged, 0)
    assert barriers(remat.Saved(prevent_cse=True)) == (fenced + merged, 0, 0)
    assert barriers(remat.Saved(NO_WO)) == (fenced, merged, merged)
    on_a_rung = remat.Saved(NO_WO, prevent_cse=True)
    assert barriers(on_a_rung) == (fenced + merged, 0, merged)
    assert on_a_rung.effective(NO_WO) == NO_WO


@pytest.mark.parametrize("rung", [("q", "k", "v", "wo", "gate", "up"),
                                  ("gate", "up"), ()], ids="+".join)
def test_delta_rule_and_plain_attention_mixers_name_the_ladders_residuals(
        rung):
    """The layer-kind decoder's ``gdn`` and ``attn`` mixers name ``q, k, v,
    wo`` and its dense feed-forward ``gate, up`` (ISSUE 40): a rung meets
    every name, wraps every layer, and changes no number of the gradient."""
    loss, params, saved = _loss_fn("olmo-hybrid-tiny", rung, xent_chunk=8,
                                   layers=("gdn", "attn"))
    grads = jax.grad(loss)(params)
    assert saved.met == set(FULL) and saved.blocks
    floor, params, _ = _loss_fn("olmo-hybrid-tiny", (), xent_chunk=8,
                                layers=("gdn", "attn"))
    want = jax.grad(floor)(params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-6)


def _sublayer(what, dtype):
    """(loss over a fenced sublayer's parameters and input, its arguments):
    a ``SwiGLU`` alone, a ``gdn`` mixer alone (its ``_delta_out``), or a
    whole ``gdn`` layer (the mixer and, behind the post-sublayer norm, the
    ``SwiGLU``)."""
    from tony_tpu.models import hybrid

    cfg = get_model("olmo-hybrid-tiny", dtype=dtype, layers=("gdn",)).cfg
    module = {"swiglu": lambda: hybrid.SwiGLU(cfg, 128),
              "gdn": lambda: hybrid.GDN(cfg),
              "gdn-layer": lambda: hybrid.HybridLayer(cfg, "gdn", 0)}[what]()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, cfg.dim), dtype)
    params = module.init(jax.random.PRNGKey(1), x)["params"]

    def loss(params, x):
        out = module.apply({"params": params}, x)
        out = out if what == "swiglu" else out[0]
        return jnp.sum(jnp.square(out.astype(jnp.float32)))
    return loss, (params, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("what", ["swiglu", "gdn", "gdn-layer"])
def test_the_fence_is_the_identity_in_value_and_every_gradient(
        what, dtype, monkeypatch):
    """``remat.fence`` (ISSUE 42) only says where an array is made: the
    loss and the gradient of every parameter and of the input are those of
    the sublayer without it, bit for bit in both dtypes for a ``SwiGLU``
    and for a ``gdn`` mixer. In a whole layer the loss is still bit for
    bit and the gradients are the same sums in another order: the layer's
    ``x + norm1(mixer)`` is read by the residual and by ``w_gate`` and
    ``w_up``, and the fence on the FFN's input adds those two products'
    cotangents to each other before the residual's (float addition does
    not associate: 2e-6 of a gradient's norm in float32, 0.7% in bfloat16,
    where PR 41's rungs differ from their floor by 0.6-1%)."""
    loss, args = _sublayer(what, dtype)
    got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(*args)
    monkeypatch.setattr(remat, "fence", lambda x: x)
    loss, args = _sublayer(what, dtype)
    text = str(jax.make_jaxpr(jax.grad(loss))(*args))
    assert "optimization_barrier" not in text
    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(*args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if what != "gdn-layer":
            np.testing.assert_array_equal(a, b)
        else:
            assert np.linalg.norm(a - b) <= (
                1e-5 if dtype == jnp.float32 else 2e-2) * np.linalg.norm(b)


# Fence sites a layer: ``SwiGLU`` (its input, ``gate``, ``up``, ``silu(gate)
# * up``, its output) and ``_delta_out`` (``y``, ``wo``'s output).
SWIGLU_FENCES, DELTA_OUT_FENCES = 5, 2


@pytest.mark.parametrize("model_name, kw, fences, dead", [
    ("olmo-hybrid-tiny", {}, 2 * SWIGLU_FENCES + DELTA_OUT_FENCES, 0),
    ("kimi-linear-tiny", {}, SWIGLU_FENCES + 2 * DELTA_OUT_FENCES, 1),
    ("hybrid-tiny", {}, 0, 0), ("llama-tiny", {"scan_layers": False}, 0, 0)])
def test_a_steps_trace_counts_the_operands_it_makes_once(
        chooser, monkeypatch, memo_dir, model_name, kw, fences, dead):
    """Each fence is three barriers in the jaxpr of the step's gradient:
    on its value in the forward, on its value again in the layer's second
    forward, on its cotangent (the layers' own barriers, under
    ``prevent_cse``, are put in when the step is lowered: none in the
    jaxpr). One is ``dead``: no backward reads the output of a pre-norm
    layer's feed-forward (Kimi Linear's dense one), so its second forward
    is not traced; a post-norm layer's (Olmo Hybrid) is read by the norm's
    backward. The step's ``Saved`` counts the sites, and the chosen step's
    timeline says so — cold and from the memo — where there are any. A
    model that never enters ``SwiGLU`` or ``_delta_out`` reads none."""
    loss, params, saved = _loss_fn(model_name, remat.LADDER[0],
                                   prevent_cse=True, xent_chunk=8, **kw)
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert text.count("optimization_barrier") == 3 * fences - dead
    assert saved.fences == fences

    monkeypatch.setattr(remat, "_device_of", lambda _s: FakeDevice(1 << 40))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    model = get_model(model_name, remat=True, xent_chunk=8, **kw)
    for start in ("cold", "warm"):
        state = train.create_train_state(model, optax.adam(1e-3), tokens,
                                         jax.random.PRNGKey(1))
        step = train.make_train_step(
            loss_of=lambda loss, b: loss,
            apply_kwargs_of=lambda b: {"targets": b["x"]})
        profiler.reset_timeline()
        step.lower(state, {"x": tokens})
        c = profiler.counters()
        assert c["remat:from_memo"] == (start == "warm")
        assert c.get("remat:operands_made_once", 0) == fences, start
        assert ("remat:operands_made_once" in c) == bool(fences)


@pytest.mark.parametrize("model_name, digest", [
    ("llama-tiny", "5beb4b71c9a3e9f4"), ("hybrid-tiny", "ec0ac400995f3d4c"),
    ("zaya-tiny", "25ee96bc7f638dc6"), ("keye-tiny", "d5ee50706b6b6322")])
def test_a_model_outside_the_fenced_sublayers_traces_the_parents_gradient(
        model_name, digest):
    """``Transformer``'s MLP and experts and the layer-kind decoder's
    ``GatedMLP`` pass no fence: the jaxpr of the step's gradient on the
    ladder's top rung is, letter for letter, the one PR 41's tree traced
    (its sha256, function addresses left out; a PR that changes these
    models' trace on purpose re-pins it from its own parent;
    ``tests/test_kimi_linear.py`` pins the four cells' steps at their real
    sizes, outside a step's ``Saved``)."""
    import hashlib

    tokens = jnp.zeros((2, 16), jnp.int32)
    model = get_model(model_name, remat=True)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
    saved = remat.Saved(remat.LADDER[0])

    def loss(p):
        with saved:
            return train.next_token_loss(
                model.apply({"params": p}, tokens), tokens)
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert saved.fences == 0 and "optimization_barrier" not in text
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_names_are_inert_outside_a_step():
    """``model.init``, the serve forward and a step on the CPU trace the
    same modules with no set active: nothing is met, nothing is kept."""
    tokens = jnp.zeros((2, 16), jnp.int32)
    model = get_model("llama-tiny", remat=True)
    state = train.create_train_state(model, optax.adam(1e-3), tokens,
                                     jax.random.PRNGKey(1))
    step = train.make_train_step(
        loss_of=lambda lg, b: train.next_token_loss(lg, b["x"]))
    profiler.reset_timeline()
    lowered = step.lower(state, {"x": tokens})            # as chip_smoke does
    assert "remat" not in " ".join(profiler.counters())
    assert lowered.compile() is not None
    _, metrics = step(state, {"x": tokens})
    assert np.isfinite(float(metrics["loss"]))
    assert remat._ACTIVE.get() is None


def test_real_step_on_a_device_with_room_builds_one_program_a_start(
        memo_dir, monkeypatch):
    """The whole path over the real compiler (the CPU's), the device's
    answer faked: cold, the richest rung is compiled by the ladder and
    the call that follows builds nothing more; warm, the memo names the
    rung and its first call is the one build. The loss is the floor's."""
    monkeypatch.setattr(remat, "_device_of", lambda _s: FakeDevice(1 << 40))
    profiler.watch_builds()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    model = get_model("llama-tiny", remat=True)
    losses = {}
    for start in ("cold", "warm", "floor"):
        state = train.create_train_state(model, optax.adam(1e-3), tokens,
                                         jax.random.PRNGKey(1))
        step = train.make_train_step(
            loss_of=lambda lg, b: train.next_token_loss(lg, b["x"]))
        if start == "floor":
            step = step.build(remat.Saved())
        profiler.reset_timeline()
        state, metrics = step(state, {"x": tokens})
        state, metrics = step(state, {"x": tokens})
        losses[start] = float(metrics["loss"])
        c = profiler.counters()
        assert c.get("programs_compiled", 0) + c.get("programs_loaded", 0) \
            == 1, (start, c)
        if start != "floor":
            assert {n for n in FULL if f"remat:saved.{n}" in c} == set(FULL)
            assert c["remat:from_memo"] == (start == "warm")
            assert c["remat:rungs_tried"] == (start == "cold")
            assert 0 < c["remat:step_bytes"] < c["remat:bytes_limit"]
    assert losses["cold"] == losses["warm"] == losses["floor"]
    assert len(list((memo_dir / "tony_remat").glob("*.json"))) == 1


def test_real_fenced_rung_is_the_program_a_warm_start_builds(
        memo_dir, monkeypatch):
    """The second walk over the real compiler (the CPU's) and real
    unrolled layers; only the compiler's refusals and the bytes are made
    up: every unfenced step refused, the top fenced rung over the margin.
    The step is then not the last candidate traced; it runs, a warm start
    builds it alone from the memo, and its loss is the floor's (in
    float32: a second forward that is not merged with the first rounds
    its bfloat16 intermediates where the compiler's fusions put them)."""
    tried = []

    def bytes_of(_compiled):
        saved = tried[-1]
        if not saved.prevent_cse:
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: faked")
        return LIMIT - remat.MARGIN + (saved.effective(saved.names) == FULL)

    monkeypatch.setattr(remat, "_device_of", lambda _s: FakeDevice(LIMIT))
    monkeypatch.setattr(remat, "step_bytes", bytes_of)
    profiler.watch_builds()
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    model = get_model("llama-tiny", remat=True, scan_layers=False,
                      dtype=jnp.float32)
    losses = {}
    for start in ("cold", "warm", "floor"):
        state = train.create_train_state(model, optax.adam(1e-3), tokens,
                                         jax.random.PRNGKey(1))
        real = train.make_train_step(
            loss_of=lambda lg, b: train.next_token_loss(lg, b["x"]))
        step = remat.ChosenStep(
            lambda saved: (tried.append(saved), real.build(saved))[1])
        if start == "floor":
            step = real.build(remat.Saved())
        del tried[:]
        profiler.reset_timeline()
        state, metrics = step(state, {"x": tokens})
        state, metrics = step(state, {"x": tokens})
        losses[start] = float(metrics["loss"])
        if start == "floor":
            continue
        c = profiler.counters()
        assert {n for n in FULL if f"remat:saved.{n}" in c} == set(NO_WO)
        assert (c["remat:prevent_cse"], c["remat:from_memo"]) == (
            1, start == "warm")
        assert c["remat:step_bytes"] + remat.MARGIN == c["remat:bytes_limit"]
        if start == "cold":
            assert [(s.prevent_cse, s.effective(s.names)) for s in tried] \
                == [(False, r) for r in RUNGS] + [
                    (True, r) for r in RUNGS[::-1]]
            assert (c["remat:rungs_tried"], c["remat:rungs_refused"],
                    c["remat:rungs_fenced"]) == (10, 5, 5)
        else:
            assert [(s.prevent_cse, s.names) for s in tried] == [(True, NO_WO)]
            assert c.get("programs_compiled", 0) \
                + c.get("programs_loaded", 0) == 1, c
            assert _rung_spans() == []
    assert losses["cold"] == losses["warm"] == losses["floor"]


def test_both_decoders_wrap_their_layers_in_the_one_helper():
    import inspect

    from tony_tpu.models import hybrid, transformer

    for mod in (hybrid, transformer):
        src = inspect.getsource(mod)
        assert "remat.block(" in src and "nn.remat(" not in src, mod
    assert not hasattr(transformer.TransformerConfig(), "remat_policy")


def test_the_step_a_cold_start_compiled_is_the_step_a_warm_start_loads(
        chooser, monkeypatch):
    """A Pallas kernel's compile-cache key holds the call stack above it
    (tests/test_compile_key.py). The ladder lowers its candidates and the
    chosen step is run from one call site, so the program the cold start
    compiled has the key the warm start asks the cache for — and a rung
    is another program than the floor."""
    import hashlib

    from jax._src import cache_key

    tokens = jnp.zeros((2, 256), jnp.int32)
    model = get_model("llama-tiny", dim=256, n_heads=2, n_kv_heads=1,
                      ffn_hidden=256, max_seq=256, attention="flash",
                      remat=True)
    state = train.create_train_state(model, optax.adamw(1e-3), tokens,
                                     jax.random.PRNGKey(0))
    real = train.make_train_step(
        loss_of=lambda lg, b: train.next_token_loss(lg, b["x"]))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(remat, "_device_of", lambda _s: FakeDevice(LIMIT))
    # jax keeps the innermost ten frames of a location; the stand-in
    # below adds one, and the site has to be among them to be seen here.
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 16)
    keys = []

    def build(saved):
        jitted = real.build(saved)

        class Keyed:
            """Lowered for the TPU (never compiled: no chip here), hashed
            as jax hashes a program for its cache."""

            def lower(self):
                return self

            def key(self, state, batch):
                module = jitted.trace(state, batch).lower(
                    lowering_platforms=("tpu",)).compiler_ir("stablehlo")
                assert str(module).count("@tpu_custom_call") == 4
                digest = hashlib.sha256(cache_key._canonicalize_ir(
                    module, cache_key.IgnoreCallbacks.NO)).hexdigest()
                keys.append((saved.effective(saved.names), digest))
                return self

            trace = __call__ = key

            def compile(self):
                return FakeCompiled(MISTRAL[saved.effective(saved.names)])

        return Keyed()

    batch, starts = {"x": tokens}, []
    try:
        for _ in ("cold: the ladder", "warm: the memo"):
            remat.ChosenStep(build)(state, batch)
            starts.append(keys[:])
            del keys[:]
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    cold, warm = starts
    assert [names for names, _ in cold] == [FULL, NO_WO, NO_WO]
    assert cold[1] == cold[2] == warm[0] and len(warm) == 1
    assert cold[0][1] != cold[1][1]
