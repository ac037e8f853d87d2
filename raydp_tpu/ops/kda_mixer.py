"""The KDA mixer's element-wise chain on both sides of its scan, as fused
kernels on the scan's own layout (``ops/delta_rule.py``: ``[b, t, h x d]``, a
head's channels one block column).

BEFORE the scan (``operands``): from the products ``a W_q``, ``a W_k``, ``a
W_v`` and ``a W_f`` of a token's normed input, each read ONCE::

    x    = silu(sum_i taps[i] p_{t - (K - 1) + i})     a channel at a time
    q    = x_q / sqrt(sum_head x_q^2 + 1e-6) Dk^-0.5   k alike, unscaled
    v    = x_v
    la   = floor sigmoid(exp(A_log)[head] (p_f + dt_bias))

``q``, ``k``, ``v`` leave in the products' dtype and the log-decay in float32,
each written ONCE where ``delta_rule_fwd`` / ``delta_rule_bwd`` read them.
AFTER it (``read_out``): the scan's ``o`` as its call wrote it, normed a head
(``o / sqrt(mean_head o^2 + eps) gain``) and gated by the head's sigmoid, is
``W_o``'s input. Float32 inside a tile exactly where ``HybridLM._kda``'s plain
``jnp`` chain is float32, the same operations in the same order; no ``[t, h,
d]`` view is formed, so XLA has no layout to change and no copy to make.

THE BACKWARD of each is a kernel too: it takes the forward's INPUTS (the
products; ``o`` and the gate), recomputes the tile's chain in VMEM and emits
the inputs' gradients and, as one row a grid step for XLA to sum, those of
``conv_w``, ``exp(A_log)`` a channel, ``dt_bias`` and ``gate_norm``. The
convolution reaches ``K - 1`` tokens back: a tile reads the ``HALO`` rows
before it through a second block of the same array, and in the backward pass,
whose tiles run last first, the first rows' gradient of a tile waits in VMEM
for the tile before it.

WHAT A CALL COSTS TO BUILD is part of its design (``PERF.md`` §6, PR 52: a
Mosaic call is compiled once an INSTANCE and traced and lowered once a layer
and pass unless something shares it). So a body is one rolled loop over
``ROWS`` rows of the tile (the code Mosaic emits goes with the rows a
statement spans), each call's entry is ONE module-level ``jax.jit`` (five
layers share a trace and a lowered function), and what a recomputed block
would need a second forward call for carries a ``checkpoint_name``
(``OPERANDS``, ``READ_OUT``) for its policy to keep.

Off the chip the same bodies run through the Pallas interpreter
(``ops/backend.py``); under a mesh each device runs the calls on its rows of
the batch. ``refused`` says why a mixer cannot take this path (None: it
can), from what it can see: the widths and the tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from raydp_tpu.ops import delta_rule
from raydp_tpu.ops.backend import pallas_interpret, per_shard_under_mesh

F32 = jnp.float32
# names a recomputed block's policy keeps: the scan's operands (its backward
# call's residuals: q, k, v and the log-decay) and ``W_o``'s input (its
# weight gradient's operand). With both kept no forward call of this module
# runs twice a step
OPERANDS, READ_OUT = "kda_operands", "kda_read_out"
# rows of a tile one turn of a body's loop works on, and heads a grid step
# where the layer's heads go in so many (else half as many, down to one):
# measured alone on the chip at the Ling cell's shape (``PERF.md`` §6, PR 52:
# the four calls together 4.73 ms a layer at 32 rows and the scan's 2 heads,
# 3.57 at 64 and 4; 128 rows buy nothing more and a body's code goes with them)
ROWS = 64
HEADS_A_STEP = 4
# rows before a tile that the convolution may read (a block of whole sublane
# tiles of a 16-bit operand); a convolution reaches K - 1 <= HALO rows back
HALO = 16
# the first rows' gradient a tile leaves for the tile before it: K - 1 <=
# CARRY rows (a float32 sublane tile)
CARRY = 8
VMEM_BYTES = 48 * 2**20


def refused(tokens: int, taps: int, key_dim: int, value_dim: int):
    """Why the fused chain cannot run a mixer of these sizes (None: it can):
    what the scan's own rule refuses on a chip (``delta_rule._refused``), and
    what the tiles here need whatever runs them."""
    if tokens % HALO:
        return (f"{tokens} tokens: a tile reads the {HALO} rows before it as "
                "one block")
    if taps - 1 > CARRY:
        return f"a convolution of {taps} taps reaches past {CARRY} rows"
    if pallas_interpret(None):
        return None
    return delta_rule._refused(tokens, key_dim, value_dim,
                               min(delta_rule.CHUNK, tokens))


def _tiles(t: int, h: int):
    """(tokens a grid step, heads a grid step, rows a turn of the loop): the
    scan's own token tiles (``delta_rule.grid_step``)."""
    tile, _ = delta_rule.grid_step(t, h, min(delta_rule.CHUNK, t))
    if t % tile or tile % HALO:
        raise ValueError(f"{t} tokens are not whole grid steps of {tile} in "
                         f"tiles of {HALO} rows")
    heads = next(n for n in (HEADS_A_STEP, HEADS_A_STEP // 2, 1) if h % n == 0)
    rows = next(n for n in (ROWS, ROWS // 2, HALO) if tile % n == 0)
    return tile, heads, rows


def _params(semantics):
    return delta_rule._params(semantics, VMEM_BYTES)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _bytes(*arrays) -> int:
    return sum(x.size * x.dtype.itemsize for x in arrays)


# ---------------------------------------------------------------------------
# what a body is made of: [rows, heads x d] float32 values

def _window(ref, halo_ref, r0, rows: int, first):
    """Rows ``r0 - HALO .. r0 + rows`` of the sequence out of a tile's block
    and the block of the ``HALO`` rows before the tile, float32; zeros
    before the sequence's first token (``first``: the tile is the first)."""
    before = ref[0, pl.ds(pl.multiple_of(jnp.maximum(r0 - HALO, 0), HALO),
                          HALO), :].astype(F32)
    halo = jnp.where(first, 0.0, halo_ref[0].astype(F32))
    before = jnp.where(r0 == 0, halo, before)
    return jnp.concatenate(
        [before, ref[0, pl.ds(r0, rows), :].astype(F32)], axis=0)


def _taken(window, taps: int):
    """What each tap multiplies: ``window``'s rows shifted K - 1 .. 0 rows
    down, the oldest first, [rows, w] each."""
    return [(pltpu.roll(window, taps - 1 - i, 0) if i < taps - 1
             else window)[HALO:] for i in range(taps)]


def _conv(taken, taps):
    """``sum_i taps[i] x_{t - (K - 1) + i}`` summed as ``_depthwise_causal``
    sums it: from zero, the oldest tap first."""
    out = 0.0
    for i, x in enumerate(taken):
        out = out + taps[i:i + 1] * x
    return out


def _by_head(x, heads: int):
    d = x.shape[1] // heads
    return [x[:, g * d:(g + 1) * d] for g in range(heads)]


def _head_sums(x, heads: int):
    """The sum over each head's channels, [rows, 1] a head."""
    return [jnp.sum(part, axis=1, keepdims=True)
            for part in _by_head(x, heads)]


def _over_heads(columns, like):
    """[rows, 1] a head as [rows, heads x d]: each head's over its channels."""
    d = like.shape[1] // len(columns)
    return jnp.concatenate(
        [jnp.broadcast_to(col, (like.shape[0], d)) for col in columns], axis=1)


def _l2(x, heads: int):
    """(x / sqrt(sum_head x^2 + 1e-6), the reciprocal root a channel)."""
    root = _over_heads([lax.rsqrt(total + 1e-6)
                        for total in _head_sums(x * x, heads)], x)
    return x * root, root


def _column_sums(x):
    return jnp.sum(x, axis=0, keepdims=True)


def _head_columns(ref_rows, first, heads: int):
    """[rows, 1] a head of the step: its column of a ``[rows, h]`` block
    whose lanes are the layer's heads (``first`` on)."""
    lane = lax.broadcasted_iota(jnp.int32, ref_rows.shape, 1)
    return [jnp.sum(jnp.where(lane == first + g, ref_rows, 0.0), axis=1,
                    keepdims=True) for g in range(heads)]


# ---------------------------------------------------------------------------
# before the scan

def _operands_kernel(pq_ref, pk_ref, pv_ref, pf_ref, hq_ref, hk_ref, hv_ref,
                     tq_ref, tk_ref, tv_ref, scale_ref, bias_ref,
                     q_ref, k_ref, v_ref, la_ref, *, heads: int, rows: int,
                     floor: float, q_scale: float):
    """Grid (batch, heads by ``heads``, token tile)."""
    first = pl.program_id(2) == 0
    taps = tq_ref.shape[0]

    def turn(i, _):
        r0 = pl.multiple_of(i * rows, rows)
        here = pl.ds(r0, rows)
        for p_ref, h_ref, t_ref, out_ref, normed, scale in (
                (pq_ref, hq_ref, tq_ref, q_ref, True, q_scale),
                (pk_ref, hk_ref, tk_ref, k_ref, True, None),
                (pv_ref, hv_ref, tv_ref, v_ref, False, None)):
            x = _conv(_taken(_window(p_ref, h_ref, r0, rows, first), taps),
                      t_ref[...])
            x = x * jax.nn.sigmoid(x)
            if normed:
                x, _ = _l2(x, heads)
            if scale is not None:
                x = x * scale
            out_ref[0, here, :] = x.astype(out_ref.dtype)
        la_ref[0, here, :] = floor * jax.nn.sigmoid(
            scale_ref[...] * (pf_ref[0, here, :].astype(F32) + bias_ref[...]))
        return 0

    lax.fori_loop(0, pq_ref.shape[1] // rows, turn, 0)


def _operands_grad_kernel(pq_ref, pk_ref, pv_ref, pf_ref, hq_ref, hk_ref,
                          hv_ref, tq_ref, tk_ref, tv_ref, scale_ref, bias_ref,
                          dq_ref, dk_ref, dv_ref, dla_ref,
                          dpq_ref, dpk_ref, dpv_ref, dpf_ref,
                          dtq_ref, dtk_ref, dtv_ref, dgate_ref,
                          q_carry, k_carry, v_carry, *, heads: int, rows: int,
                          floor: float, q_scale: float):
    """Grid (batch, heads by ``heads``, token tile), the tiles LAST FIRST:
    the gradient of the convolution's result at a tile's first rows reaches
    the tile before it, and waits for it in ``*_carry``. A turn of the
    loop recomputes its rows' chain from the products. ``dt*_ref`` take the
    step's sums over its rows for each tap (rows 0 .. K - 1 of eight),
    ``dgate_ref`` those for ``exp(A_log)`` a channel (row 0) and ``dt_bias``
    (row 1)."""
    step, steps = pl.program_id(2), pl.num_programs(2)
    first = step == steps - 1  # of the sequence
    taps, turns = tq_ref.shape[0], pq_ref.shape[1] // rows

    carries = (q_carry, k_carry, v_carry)

    @pl.when(step == 0)
    def _last_tile():
        for ref in carries:
            ref[...] = jnp.zeros_like(ref)

    for ref in (dtq_ref, dtk_ref, dtv_ref, dgate_ref):
        ref[...] = jnp.zeros_like(ref)

    def turn(j, later):
        i = turns - 1 - j
        r0 = pl.multiple_of(i * rows, rows)
        here = pl.ds(r0, rows)
        leaves = []
        for n, (p_ref, h_ref, t_ref, g_ref, dp_ref, dt_ref, normed, scale) in (
                enumerate((
                    (pq_ref, hq_ref, tq_ref, dq_ref, dpq_ref, dtq_ref, True,
                     q_scale),
                    (pk_ref, hk_ref, tk_ref, dk_ref, dpk_ref, dtk_ref, True,
                     None),
                    (pv_ref, hv_ref, tv_ref, dv_ref, dpv_ref, dtv_ref, False,
                     None)))):
            w = t_ref[...]
            taken = _taken(_window(p_ref, h_ref, r0, rows, first), taps)
            c = _conv(taken, w)
            gate = jax.nn.sigmoid(c)
            x = c * gate
            dx = g_ref[0, here, :].astype(F32)
            if scale is not None:
                dx = dx * scale
            if normed:
                # y = x r, r = (sum x^2 + eps)^-1/2: dx = r dy - x r^3 sum x dy
                _, root = _l2(x, heads)
                dx = root * dx - x * root * root * root * _over_heads(
                    _head_sums(x * dx, heads), x)
            dc = dx * gate * (1.0 + c * (1.0 - gate))
            for i_tap, x_tap in enumerate(taken):
                dt_ref[0, 0, i_tap:i_tap + 1, :] += _column_sums(dc * x_tap)
            # the rows' own gradient and that of the CARRY rows after them
            reach = jnp.concatenate([dc, later[n]], axis=0)
            dp = 0.0
            for i_tap in range(taps):
                ahead = taps - 1 - i_tap
                dp = dp + w[i_tap:i_tap + 1] * (
                    pltpu.roll(reach, rows + CARRY - ahead, 0) if ahead
                    else reach)[:rows]
            dp_ref[0, here, :] = dp.astype(dp_ref.dtype)
            leaves.append(dc[:CARRY])
        scale_row, bias_row = scale_ref[...], bias_ref[...]
        shifted = pf_ref[0, here, :].astype(F32) + bias_row
        decay = jax.nn.sigmoid(scale_row * shifted)
        dz = floor * dla_ref[0, here, :] * decay * (1.0 - decay)
        dpf_ref[0, here, :] = (dz * scale_row).astype(dpf_ref.dtype)
        dgate_ref[0, 0, 0:1, :] += _column_sums(dz * shifted)
        dgate_ref[0, 0, 1:2, :] += _column_sums(dz * scale_row)
        return tuple(leaves)

    left = lax.fori_loop(0, turns, turn, tuple(ref[...] for ref in carries))
    for ref, rows_left in zip(carries, left):
        ref[...] = rows_left


def _operands_specs(t: int, h: int, dk: int, dv: int, taps: int, down: bool):
    """((in_specs of the products, their halos, the taps and the two rows),
    a block of a result by its width, the grid's token tiles): ``down``
    walks the tiles last first."""
    tile, heads, _ = _tiles(t, h)
    tiles, halos = t // tile, tile // HALO

    def at(ti):
        return tiles - 1 - ti if down else ti

    def rows_at(d):
        return pl.BlockSpec((1, tile, heads * d),
                            lambda bi, hi, ti: (bi, at(ti), hi))

    def halo(d):
        return pl.BlockSpec((1, HALO, heads * d), lambda bi, hi, ti: (
            bi, jnp.maximum(at(ti) * halos - 1, 0), hi))

    def row(depth, d):
        return pl.BlockSpec((depth, heads * d), lambda bi, hi, ti: (0, hi))

    specs = [rows_at(dk), rows_at(dk), rows_at(dv), rows_at(dk),
             halo(dk), halo(dk), halo(dv),
             row(taps, dk), row(taps, dk), row(taps, dv),
             row(1, dk), row(1, dk)]
    return specs, rows_at, at, tiles


@functools.partial(jax.jit, static_argnames=("h", "floor", "interpret"))
def _operands_call(pq, pk, pv, pf, tq, tk, tv, scale, bias, *, h: int,
                   floor: float, interpret: bool):
    b, t, _ = pq.shape
    dk, dv, taps = pq.shape[2] // h, pv.shape[2] // h, tq.shape[0]
    tile, heads, rows = _tiles(t, h)
    specs, rows_at, _, tiles = _operands_specs(t, h, dk, dv, taps, False)
    return pl.pallas_call(
        functools.partial(_operands_kernel, heads=heads, rows=rows,
                          floor=floor, q_scale=dk ** -0.5),
        grid=(b, h // heads, tiles),
        in_specs=specs,
        out_specs=[rows_at(dk), rows_at(dk), rows_at(dv), rows_at(dk)],
        out_shape=[_sds(pq.shape, pq.dtype), _sds(pk.shape, pk.dtype),
                   _sds(pv.shape, pv.dtype), _sds(pf.shape, F32)],
        compiler_params=_params(("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=40 * (pq.size + pk.size + pv.size + pf.size),
            transcendentals=pq.size + pk.size + pv.size + pf.size,
            bytes_accessed=2 * _bytes(pq, pk, pv) + _bytes(pf) + 4 * pf.size),
        interpret=interpret,
        name="kda_operands_fwd",
    )(pq, pk, pv, pf, pq, pk, pv, tq, tk, tv, scale, bias)


@functools.partial(jax.jit, static_argnames=("h", "floor", "interpret"))
def _operands_grad_call(pq, pk, pv, pf, tq, tk, tv, scale, bias, dq, dk_, dv_,
                        dla, *, h: int, floor: float, interpret: bool):
    b, t, _ = pq.shape
    dk, dv, taps = pq.shape[2] // h, pv.shape[2] // h, tq.shape[0]
    tile, heads, rows = _tiles(t, h)
    specs, rows_at, at, tiles = _operands_specs(t, h, dk, dv, taps, True)

    def sums(d):
        return pl.BlockSpec((1, 1, CARRY, heads * d),
                            lambda bi, hi, ti: (bi, at(ti), 0, hi))

    def sums_of(d):
        return _sds((b, tiles, CARRY, h * d), F32)

    return pl.pallas_call(
        functools.partial(_operands_grad_kernel, heads=heads, rows=rows,
                          floor=floor, q_scale=dk ** -0.5),
        grid=(b, h // heads, tiles),
        in_specs=specs + [rows_at(dk), rows_at(dk), rows_at(dv), rows_at(dk)],
        out_specs=[rows_at(dk), rows_at(dk), rows_at(dv), rows_at(dk),
                   sums(dk), sums(dk), sums(dv), sums(dk)],
        out_shape=[_sds(pq.shape, pq.dtype), _sds(pk.shape, pk.dtype),
                   _sds(pv.shape, pv.dtype), _sds(pf.shape, pf.dtype),
                   sums_of(dk), sums_of(dk), sums_of(dv), sums_of(dk)],
        scratch_shapes=[pltpu.VMEM((CARRY, heads * d), F32)
                        for d in (dk, dk, dv)],
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=100 * (pq.size + pk.size + pv.size + pf.size),
            transcendentals=pq.size + pk.size + pv.size + pf.size,
            bytes_accessed=3 * _bytes(pq, pk, pv) + 2 * _bytes(pf)
            + 4 * pf.size),
        interpret=interpret,
        name="kda_operands_bwd",
    )(pq, pk, pv, pf, pq, pk, pv, tq, tk, tv, scale, bias, dq, dk_, dv_, dla)


def _operands_forward(pq, pk, pv, pf, tq, tk, tv, scale, bias, h, floor,
                      interpret):
    return tuple(_operands_call(pq, pk, pv, pf, tq, tk, tv, scale, bias, h=h,
                                floor=floor, interpret=interpret))


_operands = jax.custom_vjp(_operands_forward, nondiff_argnums=(9, 10, 11))


def _operands_fwd(*given_and_static):
    # the backward call takes the forward's inputs and nothing it made
    return _operands_forward(*given_and_static), given_and_static[:9]


def _operands_bwd(h, floor, interpret, given, grads):
    dpq, dpk, dpv, dpf, dtq, dtk, dtv, dgate = _operands_grad_call(
        *given, *grads, h=h, floor=floor, interpret=interpret)
    taps = given[4].shape[0]

    def summed(x, rows):  # over the batch and the grid's token tiles
        return jnp.sum(x, axis=(0, 1))[rows]

    return (dpq, dpk, dpv, dpf,
            *(summed(x, slice(0, taps)) for x in (dtq, dtk, dtv)),
            summed(dgate, slice(0, 1)), summed(dgate, slice(1, 2)))


_operands.defvjp(_operands_fwd, _operands_bwd)


def operands(pq, pk, pv, pf, conv_w, a_log, dt_bias, floor: float):
    """The scan's ``(q, k, v, log_alpha)``, each ``[b, t, h x d]`` (the
    first three in the products' dtype, the log-decay float32), from the
    products ``pq``, ``pk``, ``pf`` [b, t, h x dk] and ``pv`` [b, t, h x
    dv], ``conv_w`` [K, h x (2 dk + dv)] (q | k | v), ``a_log`` [h] and
    ``dt_bias`` [h x dk]. Differentiable in all seven."""
    h = a_log.shape[0]
    keys = pq.shape[2]
    scale = jnp.repeat(jnp.exp(a_log), keys // h)[None]
    static = (h, float(floor), pallas_interpret(None))
    named = per_shard_under_mesh(
        lambda *given: _operands(*given, *static),
        lambda batch: ((P(batch),) * 4 + (P(),) * 5, (P(batch),) * 4))(
            pq, pk, pv, pf, conv_w[:, :keys], conv_w[:, keys:2 * keys],
            conv_w[:, 2 * keys:], scale, dt_bias[None])
    return tuple(checkpoint_name(x, OPERANDS) for x in named)


# ---------------------------------------------------------------------------
# after the scan

def _read_out_rows(o_ref, gate_ref, gain_ref, here, first, heads: int,
                   eps: float):
    """A turn's rows: (o float32, its reciprocal root-mean-square a channel,
    the head's gate a channel, the gain's row); ``first``: the step's first
    head among the layer's."""
    o = o_ref[0, here, :].astype(F32)
    d = o.shape[1] // heads
    root = _over_heads([lax.rsqrt(total / d + eps)
                        for total in _head_sums(o * o, heads)], o)
    gate = _over_heads(_head_columns(gate_ref[0, here, :], first, heads), o)
    return o, root, gate, gain_ref[...]


def _read_out_kernel(o_ref, gate_ref, gain_ref, y_ref, *, heads: int,
                     rows: int, eps: float):
    """Grid (batch, heads by ``heads``, token tile)."""
    first = pl.program_id(1) * heads

    def turn(i, _):
        here = pl.ds(pl.multiple_of(i * rows, rows), rows)
        o, root, gate, gain = _read_out_rows(o_ref, gate_ref, gain_ref, here,
                                             first, heads, eps)
        y_ref[0, here, :] = (o * root * gain * gate).astype(y_ref.dtype)
        return 0

    lax.fori_loop(0, o_ref.shape[1] // rows, turn, 0)


def _read_out_grad_kernel(o_ref, gate_ref, gain_ref, dy_ref, do_ref,
                          dgate_ref, dgain_ref, *, heads: int, rows: int,
                          eps: float):
    """Grid (batch, heads by ``heads``, token tile). ``dgate_ref`` [1, 1,
    tile, heads]: the step's heads' columns; ``dgain_ref`` row 0 of eight:
    the step's sum over its rows."""
    dgain_ref[...] = jnp.zeros_like(dgain_ref)
    first = pl.program_id(1) * heads

    def turn(i, _):
        here = pl.ds(pl.multiple_of(i * rows, rows), rows)
        o, root, gate, gain = _read_out_rows(o_ref, gate_ref, gain_ref, here,
                                             first, heads, eps)
        d = o.shape[1] // heads
        dy = dy_ref[0, here, :].astype(F32)
        normed = o * root
        dz = dy * gate
        dgain_ref[0, 0, 0:1, :] += _column_sums(dz * normed)
        columns = _head_sums(dy * (normed * gain), heads)
        lane = lax.broadcasted_iota(jnp.int32, (rows, heads), 1)
        out = jnp.zeros((rows, heads), F32)
        for g, column in enumerate(columns):
            out = jnp.where(lane == g, column, out)
        dgate_ref[0, 0, here, :] = out
        dn = dz * gain
        # n = o r, r = (mean o^2 + eps)^-1/2: do = r dn - o r^3 mean(o dn)
        do_ref[0, here, :] = (root * dn - o * root * root * root * _over_heads(
            [total / d for total in _head_sums(o * dn, heads)], o)
        ).astype(do_ref.dtype)
        return 0

    lax.fori_loop(0, o_ref.shape[1] // rows, turn, 0)


def _read_out_specs(t: int, h: int, dv: int):
    tile, heads, _ = _tiles(t, h)

    def rows_at():
        return pl.BlockSpec((1, tile, heads * dv),
                            lambda bi, hi, ti: (bi, ti, hi))

    return [rows_at(),
            pl.BlockSpec((1, tile, h), lambda bi, hi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, heads * dv), lambda bi, hi, ti: (0, hi))], rows_at


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _read_out_call(o, gate, gain, *, eps: float, interpret: bool):
    b, t, wide = o.shape
    h = gate.shape[2]
    tile, heads, rows = _tiles(t, h)
    specs, rows_at = _read_out_specs(t, h, wide // h)
    return pl.pallas_call(
        functools.partial(_read_out_kernel, heads=heads, rows=rows, eps=eps),
        grid=(b, h // heads, t // tile),
        in_specs=specs, out_specs=rows_at(),
        out_shape=_sds(o.shape, o.dtype),
        compiler_params=_params(("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=10 * o.size, transcendentals=b * t * h,
            bytes_accessed=2 * _bytes(o) + _bytes(gate)),
        interpret=interpret,
        name="kda_read_out_fwd",
    )(o, gate, gain)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _read_out_grad_call(o, gate, gain, dy, *, eps: float, interpret: bool):
    b, t, wide = o.shape
    h = gate.shape[2]
    tile, heads, rows = _tiles(t, h)
    specs, rows_at = _read_out_specs(t, h, wide // h)
    return pl.pallas_call(
        functools.partial(_read_out_grad_kernel, heads=heads, rows=rows,
                          eps=eps),
        grid=(b, h // heads, t // tile),
        in_specs=specs + [rows_at()],
        out_specs=[rows_at(),
                   pl.BlockSpec((1, 1, tile, heads),
                                lambda bi, hi, ti: (bi, hi, ti, 0)),
                   pl.BlockSpec((1, 1, CARRY, heads * (wide // h)),
                                lambda bi, hi, ti: (bi, ti, 0, hi))],
        out_shape=[_sds(o.shape, o.dtype),
                   _sds((b, h // heads, t, heads), F32),
                   _sds((b, t // tile, CARRY, wide), F32)],
        compiler_params=_params(("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=30 * o.size, transcendentals=b * t * h,
            bytes_accessed=3 * _bytes(o) + 2 * _bytes(gate)),
        interpret=interpret,
        name="kda_read_out_bwd",
    )(o, gate, gain, dy)


def _read_out_forward(o, gate, gain, eps, interpret):
    return _read_out_call(o, gate, gain, eps=eps, interpret=interpret)


_read_out = jax.custom_vjp(_read_out_forward, nondiff_argnums=(3, 4))


def _read_out_fwd(o, gate, gain, eps, interpret):
    return _read_out_forward(o, gate, gain, eps, interpret), (o, gate, gain)


def _read_out_bwd(eps, interpret, given, dy):
    o, gate, _ = given
    do, dgate, dgain = _read_out_grad_call(*given, dy, eps=eps,
                                           interpret=interpret)
    b, t, h = gate.shape
    return (do, jnp.moveaxis(dgate, 1, 2).reshape(b, t, h),
            jnp.sum(dgain, axis=(0, 1))[:1])


_read_out.defvjp(_read_out_fwd, _read_out_bwd)


def read_out(o, gate, gate_norm, eps: float):
    """``W_o``'s input [b, t, h x dv] in ``o``'s dtype from the scan's ``o``
    [b, t, h x dv], the heads' gates ``gate`` [b, t, h] (float32, after
    their sigmoid) and the norm's gain ``gate_norm`` [dv]: each head normed
    by its root-mean-square, scaled and gated, in float32. Differentiable in
    all three."""
    gain = jnp.tile(gate_norm.astype(F32), gate.shape[2])[None]
    static = (float(eps), pallas_interpret(None))
    return checkpoint_name(per_shard_under_mesh(
        lambda *given: _read_out(*given, *static),
        lambda batch: ((P(batch), P(batch), P()), P(batch)))(o, gate, gain),
        READ_OUT)
