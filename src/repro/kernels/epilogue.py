"""Fused GEMM epilogues: post-processing applied inside the deprime store.

The paper's accumulator residency argument (sections III-V) is about never
round-tripping the output through the memory hierarchy during compute.  The
same argument extends one step past the GEMM: if the next op is a bias add,
an activation, or a residual add, folding it into the ``ki == k_steps - 1``
store means the accumulator tile goes VMEM -> epilogue -> HBM once, instead
of HBM -> VMEM -> epilogue -> HBM a second time.  This is the
post-processing fusion that Kuzma et al. and "Hello SME!" attach to their
empirically-tuned microkernels.

Contract (DESIGN.md section 4):

  * The epilogue is applied to the *accumulator-dtype* tile, after the
    alpha scale, before the out_dtype cast:
        store(cast(residual + act(bias + alpha * acc)))
  * ``apply`` is the single implementation used by the Pallas kernels
    (on the VMEM-resident tile) and by ``lowering.Accumulator.deprime``
    (the XLA/ref backends, on the full matrix), so every registered
    lowering is bit-identical at fp32.  The static ``Epilogue`` rides in
    a ``facility.Plan``; the operands travel as ``contract`` kwargs.
  * bias broadcasts along rows: shape (N,) outside the kernel, a (1, bn)
    block inside.  residual has the output shape.
  * gelu/silu are float-only; integer accumulators admit bias/relu/residual
    (all exact in int32).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# erf(x) = x * P(x^2) / Q(x^2) with x clamped to [-c, c], beyond which
# f32 erf rounds to +-1: the rational form XLA's CPU backend uses for f32
# erf, so on the CPU it equals jax.lax.erf bit for bit.  Pallas TPU has
# no lowering for lax.erf.
_ERF_P = (0.00022905065861350646, 0.0034082910107109506,
          0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_Q = (-1.1791602954361697e-7, 0.000023547966471313185,
          0.0010179625278914885, 0.014070470171167667,
          0.11098505178285362, 0.49746925110067538, 1.0)
_ERF_CLAMP = 3.7439211627767994


def erf(v):
    """Error function from mul/add/div only, so it lowers in every
    kernel.  f64 keeps ``jax.lax.erf`` (the VPU/interpret path only)."""
    if v.dtype == jnp.float64:
        return jax.lax.erf(v)
    x = jnp.clip(v.astype(jnp.float32), -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x
    p = jnp.full_like(x2, _ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * x2 + c
    q = jnp.full_like(x2, _ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * x2 + c
    return (x * p / q).astype(v.dtype)


def _gelu_exact(v):
    # Exact (erf) gelu, not the tanh approximation: the tanh form's
    # x + 0.044715*x^3 term FMA-contracts differently inside a fused kernel
    # than in an eager reference, breaking the bit-for-bit contract below.
    half = jnp.asarray(0.5, v.dtype)
    inv_sqrt2 = jnp.asarray(0.7071067811865476, v.dtype)
    return v * (half * (1.0 + erf(v * inv_sqrt2)))


ACTIVATIONS = {
    "relu": lambda v: jnp.maximum(v, jnp.zeros_like(v)),
    "gelu": _gelu_exact,
    "silu": jax.nn.silu,
}


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Static description of the fused post-processing (jit-hashable).

    The actual bias/residual operands travel separately as kernel inputs;
    this object only records *which* terms are present, so it can key the
    autotune cache and be a static jit argument.
    """

    bias: bool = False
    activation: str | None = None   # relu | gelu | silu
    residual: bool = False

    def __post_init__(self):
        if self.activation is not None and self.activation not in ACTIVATIONS:
            raise ValueError(
                f"unknown activation {self.activation!r}; "
                f"have {sorted(ACTIVATIONS)}")

    @property
    def is_identity(self) -> bool:
        return not (self.bias or self.activation or self.residual)

    @property
    def key(self) -> str:
        """Cache-key fragment, e.g. 'bias+gelu+residual' or 'none'."""
        parts = ([p for p, on in (("bias", self.bias),
                                  (self.activation, self.activation),
                                  ("residual", self.residual)) if on])
        return "+".join(parts) if parts else "none"

    def validate(self, acc_dtype, bias=None, residual=None) -> None:
        """Check operand presence and int-accumulator restrictions."""
        if self.bias != (bias is not None):
            raise ValueError(f"epilogue.bias={self.bias} but "
                             f"bias operand {'missing' if self.bias else 'given'}")
        if self.residual != (residual is not None):
            raise ValueError(f"epilogue.residual={self.residual} but "
                             f"residual operand "
                             f"{'missing' if self.residual else 'given'}")
        if (self.activation in ("gelu", "silu")
                and jnp.issubdtype(jnp.dtype(acc_dtype), jnp.integer)):
            raise ValueError(
                f"{self.activation} needs a float accumulator, got {acc_dtype}")


def apply(out: jnp.ndarray, ep: Epilogue | None,
          bias: jnp.ndarray | None = None,
          residual: jnp.ndarray | None = None) -> jnp.ndarray:
    """Apply the epilogue terms to an accumulator-dtype tile or matrix.

    Shared by the Pallas deprime stores and the XLA path — keep it free of
    anything that does not trace inside a kernel.
    """
    if ep is None or ep.is_identity:
        return out
    if ep.bias:
        out = out + bias.astype(out.dtype)
    if ep.activation:
        out = ACTIVATIONS[ep.activation](out)
    if ep.residual:
        out = out + residual.astype(out.dtype)
    return out


def make(bias=None, activation: str | None = None, residual=None) -> Epilogue:
    """Build the static Epilogue matching the operands actually supplied."""
    return Epilogue(bias=bias is not None, activation=activation,
                    residual=residual is not None)
