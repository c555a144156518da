"""Operations one train step requires, counted from a configuration's shapes.

Matrix products only (2 operations per multiply-add); elementwise work,
normalization and the optimizer are left out, and nothing recomputed counts.

- Encoder, per view: forward ``2 n sum(a_i b_i)`` over the layers' (a_i, b_i);
  backward twice that, less the input gradient of the first layer, which no
  one needs: ``6 n sum(a_i b_i) - 2 n a_0 b_0``.  Both views.
- R_off: ``C = z1^T z2`` forward ``2 n d^2``, its two input gradients ``4 n d^2``.
- Grouped R_sum (Eq. 13), per view a real DFT of every b-block as one product
  with the (b, b + 2) real/imaginary basis, ``2 n d (b + 2)``; the cross-spectra
  ``G[f] = sum_k conj(F1[k, f]) F2[k, f]`` over nb x nb block pairs and
  nf = b/2 + 1 frequencies, ``8 n nb^2 nf``.  Backward: the spectra's two input
  gradients ``16 n nb^2 nf`` and the DFT's input gradient, ``2 n d (b + 2)`` per
  view.  For q = 1 the summary vectors are synthesized back, ``2 nb^2 nf b``
  (``4 nb^2 nf b`` more backward).
- VICReg (``style`` "vic") applies the regularizer to each view against
  itself: two calls, each with one DFT and the same cross-spectra work; R_off
  costs ``6 n d^2`` per call as well (both operand slots take a gradient).
"""

from __future__ import annotations


def layers(cfg: dict) -> list[tuple[int, int]]:
    dims = [int(cfg["input_dim"])] + [int(w) for w in cfg["backbone_widths"]]
    dims += [int(cfg["projector_width"])] * int(cfg["projector_layers"])
    return list(zip(dims[:-1], dims[1:]))


def param_count(cfg: dict) -> int:
    return sum(a * b + b for a, b in layers(cfg))


def encoder_flops(cfg: dict, n: int) -> int:
    ab = layers(cfg)
    per_view = 6 * n * sum(a * b for a, b in ab) - 2 * n * ab[0][0] * ab[0][1]
    return 2 * per_view


def regularizer_flops(cfg: dict, n: int) -> int:
    d = int(cfg["projector_width"])
    calls, views = (2, 1) if cfg["style"] == "vic" else (1, 2)
    if cfg["reg"] == "off":
        return calls * 6 * n * d * d
    b = int(cfg["block_size"])
    nb, nf = -(-d // b), b // 2 + 1
    per_call = views * 2 * (2 * n * nb * b * (b + 2)) + 24 * n * nb * nb * nf
    if int(cfg["q"]) == 1:
        per_call += 6 * nb * nb * nf * b
    return calls * per_call


def step_flops(cfg: dict, n: int) -> int:
    """Required operations of one train step at batch ``n``."""
    return encoder_flops(cfg, n) + regularizer_flops(cfg, n)
