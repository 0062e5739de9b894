"""Attention operand layout: tell a `flash_attention` where its q, k and
v come from, and the projection behind it where its input comes from.

A Fluid program splits heads the way the reference's
machine_translation.py does::

    mul(X, W) [-> split] -> reshape [.., .., H, D] -> transpose [0,2,1,3]
        -> flash_attention -> transpose [0,2,1,3] -> reshape [.., .., H*D]
        -> mul(., Wo)

Where the attention lowers to a loop over tiles of the batch
(ops/attention.py, `_composed_attention`), XLA hands that `while` its
operands with T on the lanes (`[B, H, D, T]`) and cannot fuse anything
into it: a projection written `[B, T, H*D]` reaches it through a `split`
pass and a `copy` of every operand and of every cotangent (PERF.md
section 6, PR 50).  The op can write the loop's layout itself, if it
knows the projection: this pass finds the chain above by op types and
permutations and hands

  * the `flash_attention` the activations and weights its operands are
    projected from (input slots ``ProjX`` / ``ProjW``; attr ``proj``,
    four integers for each of Q, K, V: ``x, w, piece, pieces`` - indices
    into the two slots and which equal slice of the weight's columns -
    or ``-1, -1, 0, 0`` for an operand no projection was found behind;
    flat, because a fused group carries only plain lists), and
  * the output projection the attention's own result and keys: its `mul`
    becomes an `attn_out_proj` (ops/attention.py: the same ``X`` and
    ``Y``, and ``AttnOut``, ``AttnK``), so that `mul` itself knows
    nothing of attention.

No op is removed and no variable changes: every op of the chain stays,
with its documented shapes, for whoever else reads it.  Which lowering is
taken is decided where the shapes are known, by ONE rule of the
attention's own module (`ops.attention.takes_tile_loop`): on the
whole-batch and Pallas routes the new slots are ignored and the lowering
is what it was (`attn_out_proj` is `mul` there); on the tiled route the
ops compute from the new slots and the chain between is dead code for XLA
to drop.  The pass only ADDS inputs, so it needs no escape analysis; it
requires each name of a chain to have one writer, ahead of its reader in
the attention's own block, and the projection's two operands to be
written by nothing between the `mul` and the attention.
"""

__all__ = ['run']

_HEADS_PERM = [0, 2, 1, 3]


def _written_inside(program, block_idx):
    """Names a sub-block tree writes: its owner writes them, to a
    reader of the parent block."""
    names = set()
    for op in program.block(block_idx).ops:
        names.update(op.output_names())
        if op.attrs.get('sub_block') is not None:
            names |= _written_inside(program, op.attrs['sub_block'])
    return names


class _Block(object):
    """One block's ops by position, its single writers and its readers."""

    def __init__(self, block, ctx):
        self.block = block
        self.pos = {id(op): i for i, op in enumerate(block.ops)}
        self.writes = {}            # name -> positions that write it
        self.readers = {}
        for i, op in enumerate(block.ops):
            written = set(op.output_names())
            if op.attrs.get('sub_block') is not None:
                written |= _written_inside(block.program,
                                           op.attrs['sub_block'])
            for n in written:
                self.writes.setdefault(n, []).append(i)
            for n in op.input_names():
                self.readers.setdefault(n, []).append(op)
        self.multi_written = ctx.multi_written

    def producer(self, name, reader):
        """The one op that writes `name`, if it runs before `reader`."""
        at = self.writes.get(name, ())
        if name in self.multi_written or len(at) != 1 or \
                at[0] >= self.pos[id(reader)]:
            return None
        return self.block.ops[at[0]]

    def unwritten_between(self, name, first, last):
        lo, hi = self.pos[id(first)], self.pos[id(last)]
        return not any(lo < i < hi for i in self.writes.get(name, ()))


def _is_heads_transpose(op):
    return op is not None and op.type == 'transpose' and \
        list(op.attrs.get('axis', ())) == _HEADS_PERM


def _is_row_projection(op):
    """`mul` of `[B, T, M]` rows by a matrix."""
    return op is not None and op.type == 'mul' and \
        op.attrs.get('x_num_col_dims') == 2 and \
        op.attrs.get('y_num_col_dims', 1) == 1


def _extents(block, name):
    return tuple(getattr(block._find_var_recursive(name), 'shape', ()))


def _keeps_rows(shape, rows):
    """A reshape target `[b, t, ...]` that leaves alone `rows`, the `T`
    of the reshape's other side (None where that side has no such
    axis): `b` is copied or inferred and `t` copied or equal."""
    b, t = shape[0], shape[1]
    return b in (0, -1) and rows is not None and t in (0, int(rows))


def _projection(blk, attn, name):
    """The `(x, w, piece, pieces)` behind one operand `[B, H, T, D]` of
    `attn`: transpose <- reshape <- (split <-) mul; or None."""
    block = blk.block
    tr = blk.producer(name, attn)
    if not _is_heads_transpose(tr):
        return None
    rs = blk.producer(tr.inputs['X'][0], tr)
    if rs is None or rs.type != 'reshape':
        return None
    shape = [int(d) for d in rs.attrs.get('shape', ())]
    flat = rs.inputs['X'][0]
    rows = _extents(block, flat)
    if len(shape) != 4 or shape[2] <= 0 or shape[3] <= 0 or \
            not _keeps_rows(shape, rows[1] if len(rows) == 3 else None):
        return None
    width = shape[2] * shape[3]
    src, piece, pieces = blk.producer(flat, rs), 0, 1
    if src is not None and src.type == 'split':
        outs = src.outputs['Out']
        sections = list(src.attrs.get('sections') or ())
        if src.attrs.get('axis') not in (2, -1) or len(set(sections)) > 1:
            return None
        piece, pieces = outs.index(flat), len(outs)
        src = blk.producer(src.inputs['X'][0], src)
    if not _is_row_projection(src):
        return None
    x, w = src.inputs['X'][0], src.inputs['Y'][0]
    wv = block._find_var_recursive(w)
    if wv is None or len(wv.shape) != 2 or \
            int(wv.shape[1]) != pieces * width or \
            not (blk.unwritten_between(x, src, attn) and
                 blk.unwritten_between(w, src, attn)):
        return None
    return x, w, piece, pieces


def _add_input(op, slot, names):
    op.input_is_list[slot] = isinstance(names, list)
    op.inputs[slot] = names if isinstance(names, list) else [names]


def _output_projection(blk, attn):
    """The `mul` that reads `attn`'s result `[B, H, T, D]` through
    transpose [0,2,1,3] -> reshape [.., T, H*D], or None."""
    out = attn.outputs['Out'][0]
    heads = _extents(blk.block, out)
    if len(heads) != 4:
        return None
    for tr in blk.readers.get(out, ()):
        if not _is_heads_transpose(tr) or \
                blk.producer(out, tr) is not attn:
            continue
        for rs in blk.readers.get(tr.outputs['Out'][0], ()):
            if rs.type != 'reshape' or blk.producer(
                    rs.inputs['X'][0], rs) is not tr:
                continue
            shape = [int(d) for d in rs.attrs.get('shape', ())]
            merged = rs.outputs['Out'][0]
            if len(shape) != 3 or \
                    shape[2] != int(heads[1]) * int(heads[3]) or \
                    not _keeps_rows(shape, heads[2]):
                continue
            for mul in blk.readers.get(merged, ()):
                if _is_row_projection(mul) and \
                        mul.inputs['X'] == [merged] and \
                        blk.producer(merged, mul) is rs:
                    return mul
    return None


def run(program, ctx):
    stats = {'attentions': 0, 'operands': 0, 'outputs': 0}
    for block in program.blocks:
        attns = [op for op in block.ops if op.type == 'flash_attention'
                 and 'ProjX' not in op.inputs]
        if not attns:
            continue
        blk = _Block(block, ctx)
        for attn in attns:
            xs, ws, proj = [], [], []
            for slot in ('Q', 'K', 'V'):
                found = _projection(blk, attn, attn.inputs[slot][0])
                if found is None:
                    proj += [-1, -1, 0, 0]
                    continue
                x, w, piece, pieces = found
                if x not in xs:
                    xs.append(x)
                if w not in ws:
                    ws.append(w)
                proj += [xs.index(x), ws.index(w), piece, pieces]
            if xs:
                _add_input(attn, 'ProjX', xs)
                _add_input(attn, 'ProjW', ws)
                attn.attrs['proj'] = proj
                stats['attentions'] += 1
                stats['operands'] += sum(1 for p in proj[::4] if p >= 0)
            mul = _output_projection(blk, attn)
            if mul is not None:
                mul.type = 'attn_out_proj'
                _add_input(mul, 'AttnOut', attn.outputs['Out'][0])
                _add_input(mul, 'AttnK', attn.inputs['K'][0])
                stats['outputs'] += 1
            if xs or mul is not None:
                program._bump()
    return stats
