"""The learner's packed minibatch read (``learner.RowSlab``): every 32-bit
per-sample field of a flat batch in one ``uint32`` slab, read with one row
gather per minibatch.

The read must be bit-identical to reading each field as ``x[idx]``: a float
step on the way (a conversion, a matmul or one-hot select, bfloat16) would
be exact on the CPU and round on the TPU, where matmuls run at the default
precision. So besides comparing raw bits on the CPU, the structural test
checks the traced program itself: one ``uint32`` gather on the slab per
minibatch step, nothing but slicing, reshaping and bit casts between the
slab and the fields, and the fields leaving the read through one
optimization barrier. Without the barrier the TPU reduces the advantages'
minibatch statistics straight out of the slab's rows, in another order
than after a per-field read, which the CPU cannot show."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.configs.ocean import ocean_tcfg, preset
from repro.envs.ocean import OCEAN
from repro.rl.engine import TrainEngine
from repro.rl.learner import RowSlab
from repro.rl.trainer import ocean_policy_stack
from repro.telemetry import registry

T, B, M = 8, 16, 4
N = T * B


def _bits(x):
    """Raw bits of ``x`` as unsigned integers of its own width."""
    x = np.asarray(x)
    if x.dtype == np.bool_:
        return x.view(np.uint8)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


def _f32(rng, shape):
    """Random float32 bit patterns, NaN payloads, infinities, subnormals
    and negative zero included: any float step on the way would show."""
    return rng.integers(0, 2**32, size=shape, dtype=np.uint32).view(
        np.float32)


def _fields(rng, obs, actions):
    return (obs, actions, _f32(rng, (N,)), _f32(rng, (N,)), _f32(rng, (N,)),
            _f32(rng, (N,)))


def _case(name, rng):
    """(fields, packed columns, fields read on their own) of a case."""
    f5 = _f32(rng, (N, 5, 5))
    i32 = lambda *s: rng.integers(-2**31, 2**31, size=(N,) + s,
                                  dtype=np.int32)
    if name == "f32_obs_int_actions":
        return _fields(rng, f5, i32(1)), 25 + 1 + 4, 0
    if name == "multidiscrete_actions":
        return _fields(rng, f5, i32(2)), 25 + 2 + 4, 0
    if name == "gaussian_actions":
        return _fields(rng, f5, _f32(rng, (N, 3))), 25 + 3 + 4, 0
    if name == "narrow_obs_leaves":
        obs = {"pixels": rng.integers(0, 256, size=(N, 4, 4), dtype=np.uint8),
               "mask": rng.integers(0, 2, size=(N, 3)).astype(bool),
               "vec": f5}
        return _fields(rng, obs, i32(1)), 25 + 1 + 4, 2
    raise KeyError(name)


def _perms(key, n_loc, blocks, to_global=lambda p, s: p):
    """One epoch's minibatch indices, (M, rows): the learner's layout,
    with ``blocks`` > 1 the single-device S-block emulation (every block
    permuted on its own, minibatch m the union of every block's m-th
    slice)."""
    return jnp.concatenate(
        [to_global(jax.random.permutation(jax.random.fold_in(key, s), n_loc),
                   s).reshape(M, n_loc // M) for s in range(blocks)], axis=1)


@pytest.mark.parametrize("name", ["f32_obs_int_actions",
                                  "multidiscrete_actions", "gaussian_actions",
                                  "narrow_obs_leaves"])
def test_packed_read_is_bit_identical_to_per_field_reads(name):
    rng = np.random.default_rng(0)
    fields, columns, separate = _case(name, rng)
    idxs = _perms(jax.random.PRNGKey(1), N, 1)

    @jax.jit
    def reads(fields, idx):
        rows = RowSlab(fields)
        return rows.read(idx), jax.tree.map(lambda x: x[idx], fields)

    rows = RowSlab(jax.tree.map(jnp.asarray, fields))
    assert (rows.columns, rows.separate) == (columns, separate)
    for idx in idxs:
        packed, plain = reads(fields, idx)
        assert jax.tree.structure(packed) == jax.tree.structure(plain)
        for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(plain)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(_bits(a), _bits(b))


def test_packed_read_is_bit_identical_under_block_emulation():
    """``num_shards=2`` on one device: rows of both env blocks meet in each
    minibatch through the learner's block-local → global index map."""
    rng = np.random.default_rng(2)
    fields, _, _ = _case("f32_obs_int_actions", rng)
    S = 2
    n_block = B // S
    to_global = lambda p, s: (p // n_block) * B + s * n_block + p % n_block
    idxs = _perms(jax.random.PRNGKey(3), T * n_block, S, to_global)
    assert sorted(np.asarray(idxs).ravel().tolist()) == list(range(N))
    rows = jax.jit(lambda f, i: RowSlab(f).read(i))
    for idx in idxs:
        for a, x in zip(jax.tree.leaves(rows(fields, idx)), fields):
            np.testing.assert_array_equal(_bits(a), _bits(np.asarray(x)[idx]))


# ----------------------------- the traced learn -----------------------------

def _squared_update():
    """The cell's Squared PPO update (preset widths, 4 epochs x 4
    minibatches), shrunk to 16 envs x unroll 8, and its arguments."""
    p = preset("squared")
    em, dist, pol = ocean_policy_stack(OCEAN["squared"](), hidden=p.hidden,
                                       recurrent=p.recurrent, conv=p.conv)
    tcfg = ocean_tcfg("squared", num_envs=B, unroll_length=T)
    eng = TrainEngine(em, pol, tcfg, dist, key=jax.random.PRNGKey(0),
                      kernel_mode="ref")
    return eng._update, (eng.ts, eng.rc, jax.random.PRNGKey(1)), tcfg


def _subs(eqn):
    """(sub-jaxpr, the operands bound to its inputs) of a higher-order
    equation."""
    p = eqn.params
    if eqn.primitive.name == "while":
        return [(p["body_jaxpr"].jaxpr, eqn.invars[p["cond_nconsts"]:])]
    if eqn.primitive.name == "cond":
        return [(b.jaxpr, eqn.invars[1:]) for b in p["branches"]]
    return [(v.jaxpr if isinstance(v, ClosedJaxpr) else v, eqn.invars)
            for v in p.values() if isinstance(v, (ClosedJaxpr, Jaxpr))]


def _slab_uses(jaxpr):
    """Every equation that takes the slab, or a value read out of it before
    its bit cast back, as an operand: ``[(eqn, lengths of the scans around
    it)]``; the slab's ``concatenate``; and what takes each field after its
    bit cast back (and reshape), by primitive name."""
    tracked, uses, slabs = set(), [], []
    fields, field_uses = set(), []

    def walk(jx, scans):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            ins = [v for v in eqn.invars
                   if not isinstance(v, Literal) and v in tracked]
            subs = _subs(eqn)
            if any(not isinstance(v, Literal) and v in fields
                   for v in eqn.invars):
                if name == "reshape":
                    fields.update(eqn.outvars)
                else:
                    field_uses.append(name)
            if ins and not subs:
                uses.append((eqn, scans))
                if name == "bitcast_convert_type":
                    fields.update(eqn.outvars)
                else:
                    tracked.update(eqn.outvars)
            if (name == "concatenate"
                    and eqn.outvars[0].aval.dtype == jnp.uint32):
                slabs.append(eqn)
                tracked.update(eqn.outvars)
            for sub, operands in subs:
                for iv, op in zip(sub.invars, operands):
                    if not isinstance(op, Literal) and op in tracked:
                        tracked.add(iv)
                inner = scans + ((eqn.params["length"],)
                                 if name == "scan" else ())
                walk(sub, inner)
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    if not isinstance(sv, Literal) and sv in tracked:
                        tracked.add(ov)

    walk(jaxpr, ())
    return uses, slabs, field_uses


def test_learn_reads_each_minibatch_with_one_uint32_row_gather():
    update, args, tcfg = _squared_update()
    uses, slabs, field_uses = _slab_uses(jax.make_jaxpr(update)(*args).jaxpr)
    assert len(slabs) == 1
    slab = slabs[0].outvars[0].aval
    assert (slab.shape, slab.dtype) == ((N, 30), jnp.uint32)

    steps = tcfg.update_epochs * tcfg.num_minibatches
    gathers = [(e, s) for e, s in uses if e.primitive.name == "gather"]
    assert len(gathers) == 1
    (gather, scans), = gathers
    assert gather.invars[0].aval.dtype == jnp.uint32
    assert gather.invars[0].aval.shape == (N, 30)
    assert gather.outvars[0].aval.shape == (N // tcfg.num_minibatches, 30)
    assert scans[-1] == steps           # inside the minibatch scan, once

    # between the slab and the fields only data movement: no conversion,
    # no matmul, no arithmetic on the bit-cast values
    prims = {e.primitive.name for e, _ in uses}
    assert not prims & {"dot_general", "convert_element_type"}
    assert prims <= {"gather", "slice", "reshape", "squeeze",
                     "bitcast_convert_type"}
    assert "bitcast_convert_type" in prims
    # every field leaves the read through one barrier, so the loss reduces
    # over its own buffer, as after a per-field read (see RowSlab.read)
    assert field_uses == ["optimization_barrier"]


def test_tracing_learn_sets_the_packing_gauges():
    reg = registry()
    for g in ("learner.rows_packed_columns", "learner.rows_separate_fields"):
        reg.gauge(g).set(-1)
    update, args, _ = _squared_update()
    jax.make_jaxpr(update)(*args)
    gauges = reg.snapshot()["gauges"]
    assert gauges["learner.rows_packed_columns"] == 30
    assert gauges["learner.rows_separate_fields"] == 0
