"""What the backward keeps instead of recomputing, chosen from the compiled
step's own memory account (ISSUE 30).

Every block of both decoders sits in ``nn.remat``: only its input survives
the forward and the backward runs the block again. That was sized for a
chip with no room; where there is room, keeping a matmul's output is worth
the matmul. Which outputs fit cannot be reckoned from shapes (the costs do
not add up: PERF.md §7), so the program asks the compiler:

* the models **name** the candidates where they are made (:func:`name`, a
  ``checkpoint_name``): ``q``, ``k``, ``v``, ``wo``, ``gate``, ``up``,
  ``flash_out``, ``flash_lse``, ``sel``. A name costs nothing until a policy asks
  for it. One more name is no candidate: ``index_grad``, the gradients an
  indexer's loss takes in its forward (tony_tpu.ops.indexer: three small
  matrices a layer against a second run of the whole loss), which a model
  that has an indexer keeps in every step, the floor included;
* :func:`block` is the one ``nn.remat`` both decoders wrap their layer in;
  it keeps the names of the :class:`Saved` the step entered around the
  model's trace and the names the model tells it to keep always, and
  nothing else outside a step (``model.init``, serving);
* :class:`ChosenStep` is what ``make_train_step`` returns: at its first
  call it walks :data:`LADDER`, richest set first, compiles each candidate
  step and takes the first whose ``memory_analysis()`` total leaves
  :data:`MARGIN` under the device's ``bytes_limit``. A refused compile
  (``RESOURCE_EXHAUSTED``) is a step down; the empty set is the floor and
  is today's program. Where the compiler refuses the floor too, the
  ladder is walked a second time under ``prevent_cse`` (XLA may merge an
  unrolled layer's second forward with its first, and then holds every
  layer's temporaries to the backward, whatever the rung keeps; barriers
  keep them two): the fenced floor first, the last program there is,
  then the rungs from the poorest upwards while each compiles and leaves
  the margin. The last that did is the step, so a step with no room
  above its fenced floor learns that from one more compile. The answer
  is remembered beside the persistent compile cache, so a warm start
  builds one program and no refused compile is repeated. A backend that
  reports no limit (the CPU) gets the floor;
* :func:`fence` says where an array is made: a sublayer of an unrolled
  layer puts it on the operands of its weight-gradient products, which
  XLA would otherwise make again inside each product, for every tile of a
  matrix-shaped result (ISSUE 42). It is on every step of a model that
  uses it, whatever the ladder keeps.

Processes of one job must agree on the set (they run one SPMD program):
they do, because each reads the same compiler and the same kind of device.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import hashlib
import json
import logging
import os
import re
from pathlib import Path
from typing import Any, Callable, Iterable, Optional, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

from tony_tpu import profiler

_log = logging.getLogger(__name__)

# Richest first, by the recomputation each removes in the dense block
# (PERF.md §6 PR 30): gate/up are the MLP's two recomputed matmuls, q/k/v
# the three projections, wo the fourth. The flash residuals are named but
# on no rung: they cost 1.25 GiB for two Mistral layers and buy 3.4 ms.
# ``sel``, a learned selection's bits (tony_tpu.ops.indexer: 1/8 byte a
# causal pair against a second scoring and 32-pass threshold of the whole
# triangle in the backward), is on every rung and is the last to go: a
# model without an indexer never meets the name, and its rungs are these
# without it (``Saved.effective``). ``index_grad`` is on no rung and not
# the ladder's to give up: a model with an indexer hands it to ``block``
# itself, for every step (9 MB a layer; without it the indexer's loss
# runs twice), and the floor stays the empty set — ``kept()`` does not
# count it and the memo's key does not hold it.
LADDER: Tuple[Tuple[str, ...], ...] = (
    ("sel", "q", "k", "v", "wo", "gate", "up"),
    ("sel", "q", "k", "v", "gate", "up"),
    ("sel", "gate", "up"),
    ("sel", "q", "k", "v"),
    ("sel",),
)
FLOOR: Tuple[str, ...] = ()

# Bytes that must stay free between the compiled step's total (arguments +
# temporaries + outputs - aliased: everything the step holds at its
# fullest, the state included) and the device's ``bytes_limit``: room for
# what the process holds beside the step while the runtime keeps the
# step's scratch reserved. Read on the v5e (PERF.md §6 PR 30, limit 15.748
# GiB): with 0.43 GiB left (Mistral cell, 15.321) a second copy of the
# weights beside the state no longer fits and the runtime has to give the
# reservation up and take it again; with 0.75 (chip_smoke's reference
# check), 0.90 (the phi cell), 0.98 (chip_smoke's job, saving while it
# trains) and 1.05 (the Mistral cell, 14.695: 0.24 GiB still free at that
# point) everything fits. The constant is the smallest headroom that held.
MARGIN = 768 << 20

_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "tony_remat_saved", default=None)


class Saved:
    """The names one step's backward keeps, and whether its layers'
    second forward is fenced off from the first (``prevent_cse``: the
    ladder's second walk, for a step whose unfenced floor the compiler
    refused); entered around the model's trace. Also the
    trace's witness: which names the model met under it and how many
    layers :func:`block` wrapped, how many :func:`fence` sites it
    passed."""

    def __init__(self, names: Iterable[str] = FLOOR,
                 prevent_cse: bool = False):
        self.names = tuple(names)
        self.prevent_cse = bool(prevent_cse)
        self.met: set = set()
        self.blocks = 0
        self.fences = 0

    def __enter__(self) -> "Saved":
        self._token = _ACTIVE.set(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACTIVE.reset(self._token)

    def effective(self, rung: Iterable[str]) -> Tuple[str, ...]:
        """``rung`` without the names this trace never met (another
        decoder's, or a model with ``remat=False``): keeping them is the
        program without them."""
        return tuple(n for n in rung if self.blocks and n in self.met)


def name(x: jax.Array, tag: str) -> jax.Array:
    """``x`` as the residual ``tag``: kept by a step whose set names it."""
    active = _ACTIVE.get()
    if active is not None:
        active.met.add(tag)
    return checkpoint_name(x, tag)


@jax.custom_vjp
def _fenced(x):
    return jax.lax.optimization_barrier(x)


_fenced.defvjp(lambda x: (jax.lax.optimization_barrier(x), None),
               lambda _, ct: (jax.lax.optimization_barrier(ct),))


def fence(x: jax.Array) -> jax.Array:
    """``x``, made once: an identity whose value and whose cotangent each
    pass ``optimization_barrier``. XLA fuses nothing across a barrier, so
    the product that reads ``x`` (or, in the backward, the products that
    read its cotangent) reads an array in HBM and not the elementwise
    recipe that makes it. That is what a weight-gradient product of an
    unrolled layer needs: its result is a matrix's shape, it walks many
    result tiles, and a fused producer over a ``[tokens, .]`` operand is
    run again for each (PERF.md section 6, PR 42). Counted on the step's
    :class:`Saved` (``remat:operands_made_once`` on its timeline)."""
    active = _ACTIVE.get()
    if active is not None:
        active.fences += 1
    return _fenced(x)


def kept() -> Tuple[str, ...]:
    """The names the step being traced keeps, of those its model has met
    so far; empty outside a step and at the floor."""
    active = _ACTIVE.get()
    return active.effective(active.names) if active is not None else FLOOR


def block(layer_cls, always: Tuple[str, ...] = ()):
    """``layer_cls`` under ``nn.remat``: its backward recomputes the layer
    from its input, but for the names the step's :class:`Saved` keeps and
    the names the model says its layers ``always`` keep (on no rung: the
    step's set, :func:`kept` and the ladder do not hear of them; a step's
    timeline does, as ``remat:saved.<name>``). A model that has none gets
    the policy it would get without the argument. The second forward may
    be merged with the first (``prevent_cse=False``: under ``nn.scan``
    nothing can be, and unrolled layers whose step fits gain what XLA
    merges) unless the step's :class:`Saved` says otherwise: the steps
    of :class:`ChosenStep`'s second walk, floor and rungs alike, taken
    when the unfenced floor itself is refused (the 32k Kimi Linear step's
    five unrolled layers read 32.9 GB merged, 14.76 GiB fenced: PERF.md
    section 6, PR 38; the 16k Olmo Hybrid step keeps ``q, k, v, wo,
    gate, up`` under the fence: PR 41)."""
    import flax.linen as nn

    active = _ACTIVE.get()
    prevent_cse = active is not None and active.prevent_cse
    names = (active.names if active is not None else FLOOR) + tuple(always)
    if active is not None:
        active.blocks += 1
        for n in always:
            profiler.count_once(f"remat:saved.{n}", 1)
    policy = jax.checkpoint_policies.save_only_these_names(*names) \
        if names else None
    return nn.remat(layer_cls, prevent_cse=prevent_cse, policy=policy)


def step_bytes(compiled) -> int:
    """What a compiled step holds at its fullest, by the compiler."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes)


def _device_of(state) -> Any:
    """The (first local) device the state lives on."""
    for leaf in jax.tree.leaves(state):
        if isinstance(leaf, jax.Array):
            return min(leaf.sharding.addressable_devices, key=lambda d: d.id)
    return jax.local_devices()[0]


def _bytes_limit(device) -> Optional[int]:
    return (device.memory_stats() or {}).get("bytes_limit")


@functools.lru_cache(maxsize=1)
def _package_digest() -> str:
    """The package's source: a changed model is another step."""
    h = hashlib.sha256()
    root = Path(__file__).resolve().parent
    for path in sorted(root.rglob("*.py")):
        h.update(path.relative_to(root).as_posix().encode())
        h.update(path.read_bytes())
    return h.hexdigest()


def memo_path(state, batch, mesh, device, limit: int,
              extra: Any = None) -> Optional[Path]:
    """Where the choice for this step is remembered: beside the persistent
    compile cache, under a key of everything the choice depends on. None
    where no cache directory is configured."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not (cache_dir and jax.config.jax_enable_compilation_cache):
        return None
    import jaxlib

    leaves = jax.tree.leaves(state)
    aval = lambda x: (tuple(jax.numpy.shape(x)),
                      str(jax.numpy.result_type(x)))
    apply_fn = getattr(state, "apply_fn", None)
    model = getattr(apply_fn, "__self__", apply_fn)
    key = json.dumps({
        "model": re.sub(r" at 0x[0-9a-f]+", "", repr(model)),
        "state": [aval(x) for x in leaves],
        "state_bytes": sum(getattr(x, "nbytes", 0) for x in leaves),
        "batch": [(jax.tree_util.keystr(p), aval(x)) for p, x in
                  jax.tree_util.tree_leaves_with_path(batch)],
        "mesh": None if mesh is None else sorted(mesh.shape.items()),
        "device": device.device_kind, "bytes_limit": limit,
        "versions": [jax.__version__, jaxlib.__version__,
                     device.client.platform_version],
        "ladder": LADDER, "margin": MARGIN, "extra": extra,
        "source": _package_digest(),
    }, sort_keys=True, default=str)
    return Path(cache_dir) / "tony_remat" / (
        hashlib.sha256(key.encode()).hexdigest() + ".json")


def _read_memo(path: Optional[Path]) -> Optional[dict]:
    if path is None:
        return None
    try:
        found = json.loads(path.read_text())
    except (OSError, ValueError):
        return None
    ok = isinstance(found, dict) and isinstance(found.get("saved"), list)
    return found if ok else None


def _write_memo(path: Optional[Path], choice: dict) -> None:
    if path is None:
        return
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp")
        tmp.write_text(json.dumps(choice))
        os.replace(tmp, path)
    except OSError as e:        # a read-only cache: choose again next time
        _log.debug("remat choice not remembered: %s", e)


def _publish(choice: dict, limit: int, from_memo: bool) -> None:
    """The choice on the task's timeline (first step of the process wins)
    and in the plan registry."""
    for n in choice["saved"]:
        profiler.count_once(f"remat:saved.{n}", 1)
    profiler.count_once("remat:step_bytes", choice["step_bytes"])
    profiler.count_once("remat:bytes_limit", limit)
    profiler.count_once("remat:rungs_tried",
                        0 if from_memo else len(choice["rungs"]))
    profiler.count_once("remat:rungs_refused", 0 if from_memo else sum(
        r["bytes"] is None for r in choice["rungs"]))
    profiler.count_once("remat:rungs_fenced", 0 if from_memo else sum(
        r["prevent_cse"] for r in choice["rungs"]))
    profiler.count_once("remat:from_memo", int(from_memo))
    if choice.get("prevent_cse"):
        profiler.count_once("remat:prevent_cse", 1)
    if choice.get("operands_made_once"):
        profiler.count_once("remat:operands_made_once",
                            choice["operands_made_once"])
    profiler.record("remat", "train_step", bytes_limit=limit, margin=MARGIN,
                    from_memo=from_memo, **choice)
    _log.info("remat: backward keeps %s; step %.3f of %.3f GiB%s",
              list(choice["saved"]) or "nothing",
              choice["step_bytes"] / 2**30, limit / 2**30,
              " (remembered)" if from_memo else "")


class ChosenStep:
    """A train step ``(state, batch) -> (state, metrics)`` that settles,
    at its first call, which residuals its backward keeps.

    ``build(saved)`` gives the jitted step whose model traces under
    ``saved`` (public: a test or a probe builds one rung with it). Calls,
    ``lower`` and ``trace`` go to the chosen step; the choice is made
    once, for the first call's shapes."""

    def __init__(self, build: Callable[[Saved], Any], mesh=None,
                 memo_extra: Any = None):
        self.build = build
        self._mesh = mesh
        self._memo_extra = memo_extra
        self._fn = None

    def __call__(self, state, batch):
        return self._enter("__call__", state, batch)

    def lower(self, state, batch):
        return self._enter("lower", state, batch)

    def trace(self, state, batch):
        return self._enter("trace", state, batch)

    def _enter(self, how: str, state, batch):
        """The one call site of the jitted steps. A candidate is traced
        and the chosen step is run from the SAME line of the same frame:
        the call stack above a Pallas kernel is part of its compile-cache
        key (``util.enable_compile_cache``), so the program the ladder
        compiled cold is the program a warm start loads."""
        fn, picking = (self._fn, None) if self._fn is not None \
            else self._advance(self._pick(state, batch), None)
        with (jax.set_mesh(self._mesh) if self._mesh is not None
              else contextlib.nullcontext()):
            while True:
                try:
                    out = getattr(fn, "trace" if picking else how)(
                        state, batch)
                except BaseException:
                    if picking is not None:
                        picking.close()     # the rung's span ends here
                    raise
                if picking is None:
                    return out
                fn, picking = self._advance(picking, out)

    def _advance(self, picking, traced):
        try:
            return picking.send(traced), picking
        except StopIteration as picked:
            self._fn = picked.value
            return self._fn, None

    def _pick(self, state, batch):
        """Generator: yields candidate steps, is sent each one's trace,
        returns the chosen step."""
        device = _device_of(state)
        limit = _bytes_limit(device)
        if not limit:
            return self.build(Saved())
        path = memo_path(state, batch, self._mesh, device, limit,
                         self._memo_extra)
        found = _read_memo(path)
        if found is not None:
            _publish(found, limit, from_memo=True)
            return self.build(Saved(found["saved"],
                                    found.get("prevent_cse", False)))
        # The first candidate's trace also tells which names this model
        # has; rungs that differ only in names it lacks are one rung, and
        # the first candidate is the program of its own effective rung.
        first = Saved(LADDER[0])
        fn, reading = yield from self._rung(first, first, limit)
        readings = [reading]
        rungs = list(dict.fromkeys(map(first.effective, LADDER + (FLOOR,))))
        for rung in rungs[1:]:
            if readings[-1]["fits"]:
                break
            fn, reading = yield from self._rung(Saved(rung), first, limit)
            readings.append(reading)
        if not readings[-1]["fits"]:
            # The floor was refused: the rungs again, each layer's second
            # forward fenced off from its first. From the floor upwards,
            # so that a step with no room above its floor finds out in
            # one compile; the last rung that fitted is the step.
            fn, reading = yield from self._rung(
                Saved(FLOOR, prevent_cse=True), first, limit)
            readings.append(reading)
            for rung in reversed([r for r in rungs if r != FLOOR]):
                candidate, richer = yield from self._rung(
                    Saved(rung, prevent_cse=True), first, limit)
                readings.append(richer)
                if not richer["fits"]:
                    break
                fn, reading = candidate, richer
        choice = {"saved": reading["saved"], "step_bytes": reading["bytes"],
                  "prevent_cse": reading["prevent_cse"],
                  "operands_made_once": first.fences, "rungs": readings}
        _write_memo(path, choice)
        _publish(choice, limit, from_memo=False)
        return fn

    def _rung(self, saved: Saved, first: Saved, limit: int):
        """Generator: one rung — its candidate yielded for tracing, the
        trace compiled, its bytes read — under the set-up span
        ``tony:remat_rung`` (attrs ``saved``, ``bytes``: None for a
        refused compile, ``fits``, ``prevent_cse``). A refused compile is
        a step down; a floor that compiles fits, whatever the margin
        says; the fenced floor is the last program there is, so its
        refusal is the step's error. Returns the candidate and its
        reading."""
        with profiler.span("tony:remat_rung") as sp:
            fn = self.build(saved)
            trace = yield fn
            rung = first.effective(saved.names)
            try:
                total = step_bytes(trace.lower().compile())
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e) or (
                        saved.prevent_cse and rung == FLOOR):
                    raise
                total = None
            fits = total is not None and (rung == FLOOR
                                          or total + MARGIN <= limit)
            sp.attrs.update(saved=",".join(rung), bytes=total, fits=fits,
                            prevent_cse=saved.prevent_cse)
        return fn, {"saved": list(rung), "bytes": total, "fits": fits,
                    "prevent_cse": saved.prevent_cse}
