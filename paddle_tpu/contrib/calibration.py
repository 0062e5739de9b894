"""Post-training int8 calibration.

Parity: reference contrib/int8_inference/utility.py `Calibrator` (KL
calibration after the TensorRT 8-bit recipe, gtc 2017 s7310).  The
reference walks conv ops and mutates MKLDNN attrs; here calibration is
backend-neutral program surgery: sample the inputs of quantizable ops over
calibration batches, pick per-tensor scales (KL-divergence search or
abs-max), then insert `quantize_dequantize_fixed_scale` ops so the
deployed program simulates int8 numerics on the MXU, and pack weights to
int8 scope arrays via QuantizeTranspiler.convert_to_int8.
"""
import numpy as np

from ..core.framework import Operator, Parameter

__all__ = ['Calibrator', 'kl_scale']

_QUANTIZABLE = {'mul', 'matmul', 'conv2d', 'conv2d_transpose'}


def kl_scale(samples, bins=2048, dst_bins=255):
    """Optimal symmetric quantization threshold by KL-divergence search
    (vectorized re-derivation of the TensorRT recipe the reference
    implements with Python loops at int8_inference/utility.py:599).

    samples: list of np arrays (calibration activations for ONE tensor).
    Returns the scale (clip threshold): values beyond it saturate.
    """
    x = np.abs(np.concatenate([np.asarray(s).ravel() for s in samples]))
    amax = float(x.max()) if x.size else 0.0
    if amax <= 0:
        return 1e-8
    # robust histogram range: far outliers must not stretch the binning
    # (everything beyond the range saturates into the edge bin below)
    amax = min(amax, 4.0 * float(np.percentile(x, 99.0)) + 1e-12)
    # with a sample or two a bin the divergence is counting noise that
    # grows with the bins kept, and the search lands on its 70% floor
    # whatever the data.  Few samples get few bins, down to dst_bins + 1,
    # where the only candidate left is (nearly) the observed range.
    bins = max(dst_bins + 1, min(bins, x.size // 16))
    hist, edges = np.histogram(np.minimum(x, amax), bins=bins,
                               range=(0.0, amax))
    hist = hist.astype(np.float64)
    bin_width = edges[1] - edges[0]
    total = hist.sum()
    best_i, best_kl = bins, np.inf
    nonzero = np.nonzero(hist)[0]
    # candidate thresholds keep >=70% of the observed range (the
    # reference's starting_iter guard at utility.py:609 — KL alone
    # over-clips peaked distributions), stepped for speed
    start = max(dst_bins, int(bins * 0.7))
    for i in range(start, bins + 1, 8):
        p = hist[:i].copy()
        # outliers saturate into the last NONZERO bin <= i-1 (the
        # reference skips empty-edge candidates outright, which strands
        # sparse histograms between the body and a far outlier)
        edge_cands = nonzero[nonzero < i]
        if edge_cands.size == 0:
            continue
        p[edge_cands[-1]] += hist[i:].sum()
        # quantize i bins down to dst_bins, then expand back (uniform
        # within each merged group over the nonzero source bins)
        idx = (np.arange(i) * dst_bins // i)
        q_merged = np.bincount(idx, weights=hist[:i], minlength=dst_bins)
        nz = (hist[:i] > 0).astype(np.float64)
        nz_count = np.bincount(idx, weights=nz, minlength=dst_bins)
        q = np.where(nz_count[idx] > 0,
                     q_merged[idx] / np.maximum(nz_count[idx], 1), 0.0)
        q = np.where(hist[:i] > 0, q, 0.0)
        mask = p > 0
        qm = np.where(q > 0, q, 1e-30)
        kl = float(np.sum(p[mask] * (np.log(p[mask] / total) -
                                     np.log(qm[mask] / max(q.sum(),
                                                           1e-30)))))
        if kl < best_kl:
            best_kl, best_i = kl, i
    return (best_i - 0.5) * bin_width


class Calibrator(object):
    """Collect activation statistics on calibration batches and emit an
    int8-simulating inference program.

    Usage::

        calib = Calibrator(program, scope=scope, algo='KL')
        for batch in calibration_data:
            calib.sample(exe, feed=batch)      # runs + records
        int8_prog = calib.freeze()             # calibrated program
        packed = calib.save_int8_weights()     # int8 weight artifact
    """

    def __init__(self, program, scope=None, algo='KL', activation_bits=8,
                 weight_bits=8):
        from ..core.executor import global_scope
        if algo not in ('KL', 'abs_max'):
            raise ValueError('algo must be KL or abs_max, got %r' % algo)
        self.program = program
        self.scope = scope if scope is not None else global_scope()
        self.algo = algo
        self.activation_bits = activation_bits
        self.weight_bits = weight_bits
        self._samples = {}            # var name -> [np arrays]
        self._targets = self._find_activation_inputs()

    def _find_activation_inputs(self):
        """Non-parameter float inputs of quantizable ops."""
        names = []
        block = self.program.global_block()
        for op in block.ops:
            if op.type not in _QUANTIZABLE:
                continue
            for slot_names in op.inputs.values():
                for n in slot_names:
                    v = block._find_var_recursive(n)
                    if v is None or isinstance(v, Parameter):
                        continue
                    if v.dtype in ('float32', 'bfloat16') and \
                            n not in names:
                        names.append(n)
        return names

    def sample(self, exe, feed):
        """Run one calibration batch, recording target activations."""
        vals = exe.run(self.program, feed=feed, fetch_list=self._targets)
        for n, v in zip(self._targets, vals):
            self._samples.setdefault(n, []).append(np.asarray(v))
        return vals

    def scales(self):
        """Per-tensor calibrated scales {var name: scale}."""
        out = {}
        for n, samples in self._samples.items():
            if self.algo == 'KL':
                out[n] = kl_scale(samples)
            else:
                out[n] = max(float(np.abs(s).max()) for s in samples)
        return out

    def freeze(self, program=None):
        """Return a clone of the program with fixed-scale quant/dequant
        ops at each calibrated activation (weights left fp32 in-graph;
        use save_int8_weights for the deploy artifact)."""
        program = program or self.program.clone(for_test=True)
        scales = self.scales()
        for block in program.blocks:
            new_ops = []
            rewired = {}
            for op in block.ops:
                for slot, names in list(op.inputs.items()):
                    op.inputs[slot] = [rewired.get(n, n) for n in names]
                if op.type in _QUANTIZABLE:
                    for slot, names in list(op.inputs.items()):
                        qnames = []
                        for n in names:
                            if n in scales and n not in rewired:
                                qn = n + '.int8calib'
                                block.create_var(
                                    name=qn,
                                    shape=block._find_var_recursive(
                                        n).shape,
                                    dtype='float32')
                                qop = Operator(
                                    block,
                                    'quantize_dequantize_fixed_scale',
                                    inputs={'X': n}, outputs={'Out': qn},
                                    attrs={'scale': float(scales[n]),
                                           'bit_length':
                                               self.activation_bits})
                                new_ops.append(qop)
                                rewired[n] = qn
                            qnames.append(rewired.get(n, n))
                        op.inputs[slot] = qnames
                new_ops.append(op)
            block.ops = new_ops
        program._bump()
        return program

    def save_int8_weights(self):
        """Pack quantizable weights to (int8 array, scale) pairs."""
        from .quantize import QuantizeTranspiler
        t = QuantizeTranspiler(weight_bits=self.weight_bits)
        return t.convert_to_int8(self.program, scope=self.scope)

    def apply_int8(self, program=None):
        """Emit a TRUE-int8 inference program: calibrated mul/conv2d ops
        become mul_int8/conv2d_int8 (int8×int8→int32 on the MXU;
        measured 1.24× over bf16 on v5e plus the 4× weight-memory cut —
        see ops/int8.py), reading int8-packed weights stored in the
        scope under `<param>.int8`.  The reference analog is the MKLDNN
        int8 kernel swap its calibrator performs."""
        import jax.numpy as jnp
        if self.weight_bits != 8:
            raise ValueError(
                'apply_int8 needs weight_bits=8: the int8 kernels assume '
                'the 127-range packing convention (got %d bits)'
                % self.weight_bits)
        program = program or self.program.clone(for_test=True)
        scales = self.scales()
        packed = self.save_int8_weights()
        for block in program.blocks:
            for op in block.ops:
                # matmul is excluded (transpose_x/y attrs don't map onto
                # the flattened-GEMM kernel), as is mul with a flattened
                # weight (y_num_col_dims != 1)
                if op.type not in ('mul', 'conv2d'):
                    continue
                if op.type == 'mul' and \
                        op.attrs.get('y_num_col_dims', 1) != 1:
                    continue
                w_slot = 'Filter' if op.type == 'conv2d' else 'Y'
                x_slot = 'Input' if op.type == 'conv2d' else 'X'
                wname = op.inputs.get(w_slot, [None])[0]
                xname = op.inputs.get(x_slot, [None])[0]
                if wname not in packed or xname not in scales:
                    continue
                q, wscale = packed[wname]
                int8_name = wname + '.int8'
                # the block var must exist in EVERY emitted program (the
                # executor pulls persistables from block.vars); only the
                # scope write is once-per-scope
                block.create_var(name=int8_name, shape=q.shape,
                                 dtype='int8', persistable=True)
                if int8_name not in self.scope:
                    self.scope.vars[int8_name] = jnp.asarray(q)
                op.inputs[w_slot] = [int8_name]
                op.type = op.type + '_int8'
                op.attrs = dict(op.attrs)
                op.attrs['x_scale'] = float(scales[xname])
                op.attrs['w_scale'] = float(wscale)
        program._bump()
        return program
