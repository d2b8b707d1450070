"""The SSM decode step updates its stacked state in place.

``decode_step`` carries the whole stacked state ``cache["ssm"]`` (and the
conv history) through its layer scan, and each block writes its own slice
(``mamba2.apply_mamba2``).  Compiled with the cache donated, as the
serving jobs compile it, the step then holds no whole-state buffer of its
own: no broadcast that makes a stacked output, and no copy of one into
the donated cache.  And the step is still the recurrence
``s <- s * exp(dt A) + B (dt x)`` of every layer, which a plain NumPy
decode of one token checks here.  Run eagerly (``model.eager_layers()``),
each slice write lands in place too, in the step's own copy of the
caller's state."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.configs.base import reduced
from repro.models import mamba2 as M2
from repro.models import model as M
from repro.train import steps as S


def _cfg(name):
    if name == "zamba2-1.2b-groups":
        # layer groups of 2, 2 and 1, the shared block called before the
        # second and the third: the state is carried across groups, each
        # from its own index
        return dataclasses.replace(reduced(get("zamba2-1.2b")),
                                   num_layers=5, hybrid_layer_ids=(2, 4))
    return reduced(get(name))


_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
_CALLS = re.compile(r"calls=(%[\w.-]+)")


def _whole_state_ops(text: str, shape) -> list[tuple[str, str, set]]:
    """(computation, opcode, opcodes of the fused computation with its
    root's first) of every instruction that yields an f32 array of
    ``shape``."""
    dims = ",".join(map(str, shape))
    body, roots, found, comp = {}, {}, [], None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1] if line.startswith("ENTRY") else \
                line.split()[0]
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        body.setdefault(comp, set()).add(m.group(3))
        if line.lstrip().startswith("ROOT"):
            roots[comp] = m.group(3)
        if m.group(1) == "f32" and m.group(2) == dims:
            called = _CALLS.search(line)
            found.append((comp, m.group(3),
                          called.group(1) if called else ""))
    return [(c, op, (roots.get(called, ""), body.get(called, set())))
            for c, op, called in found]


@pytest.mark.parametrize("name", ["mamba2-130m", "zamba2-1.2b",
                                  "zamba2-1.2b-groups"])
def test_donated_serve_step_neither_copies_nor_stacks_the_state(name):
    cfg = _cfg(name)
    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, batch=3, seq_len=16)
    tok = jnp.zeros((3, 1), jnp.int32)
    text = jax.jit(S.make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, cache, tok).compile().as_text()
    ops = _whole_state_ops(text, cache["ssm"].shape)
    # the update is there ...
    assert any(op == "fusion" and "dynamic-update-slice" in fused
               for _, op, (_, fused) in ops), ops
    # ... and nothing in the entry or the loop body copies the whole state
    # or broadcasts a fresh buffer of its shape
    whole = ("copy", "broadcast")
    assert not [o for o in ops if o[1] in whole
                or (o[1] == "fusion" and o[2][0] in whole)], ops


# ---------------------------------------------------------------- numbers

def _silu(v):
    return v / (1.0 + np.exp(-v))


def _rms(v, scale, eps):
    return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)


def _numpy_decode(params, cache, tok, cfg):
    """One token through every mamba2 layer in float32 NumPy, rounded to
    bfloat16 where the program keeps activations in it: the new stacked
    SSM state and conv history."""
    f = lambda a: np.asarray(a, np.float32)  # noqa: E731
    d_in = cfg.ssm_expand * cfg.d_model
    n, p, eps = cfg.ssm_state, cfg.ssm_headdim, cfg.norm_eps
    heads = d_in // p
    lay = params["layers"]
    h = _bf16(f(params["embed"]["tok"])[tok[:, 0]])           # (b, d)
    b = h.shape[0]
    ssm, conv = f(cache["ssm"]).copy(), f(cache["conv"]).copy()
    for i in range(cfg.num_layers):
        m = jax.tree.map(lambda a: f(a[i]), lay["mamba"])
        u = _bf16(_rms(h, f(lay["norm"]["scale"][i]), eps))
        proj = _bf16(u @ _bf16(m["in_proj"]))
        z, xbc, dt = np.split(proj, [d_in, 2 * d_in + 2 * n], axis=-1)
        xin = np.concatenate([conv[i], xbc[:, None]], axis=1)   # (b, W, C)
        conv[i] = xin[:, 1:]
        xbc = _bf16(_silu((xin * m["conv_w"]).sum(1) + m["conv_b"]))
        x, bm, cm = np.split(xbc, [d_in, d_in + n], axis=-1)
        x = x.reshape(b, heads, p)
        dt = np.logaddexp(0.0, dt + m["dt_bias"])               # softplus
        da = np.exp(dt * -np.exp(m["A_log"]))                   # (b, h)
        ssm[i] = (ssm[i] * da[..., None, None]
                  + bm[:, None, :, None] * _bf16(x * dt[..., None])[:, :, None])
        y = np.einsum("bn,bhnp->bhp", cm, _bf16(ssm[i])) + x * m["D"][:, None]
        g = _bf16(_rms(_bf16(y).reshape(b, d_in) * _silu(z), m["norm_scale"],
                       eps))
        h = _bf16(h + _bf16(g @ _bf16(m["out_proj"])))
    return ssm, conv


@pytest.mark.parametrize("eager", [False, True], ids=["scan", "eager"])
def test_decode_step_is_the_recurrence_of_every_layer(eager):
    cfg = _cfg("mamba2-130m")
    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, batch=3, seq_len=16)
    cache["ssm"] = jax.random.normal(jax.random.key(1), cache["ssm"].shape)
    cache["conv"] = jax.random.normal(
        jax.random.key(2), cache["conv"].shape).astype(cache["conv"].dtype)
    tok = jnp.array([[5], [17], [101]], jnp.int32)
    want_ssm, want_conv = _numpy_decode(params, cache, tok, cfg)
    if eager:
        with M.eager_layers():
            _, new = M.decode_step(params, cache, tok, cfg)
    else:
        _, new = jax.jit(lambda p, c, t: M.decode_step(p, c, t, cfg))(
            params, cache, tok)
    assert new["ssm"].shape == cache["ssm"].shape
    np.testing.assert_allclose(np.asarray(new["ssm"]), want_ssm,
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(new["conv"], np.float32),
                               want_conv, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("name", ["mamba2-130m", "zamba2-1.2b-groups"])
def test_eager_decode_updates_its_own_copy_in_place(name, monkeypatch):
    """Run eagerly, as ``serve --abft`` runs it, every slice write lands in
    the buffer it was given (no whole-state copy per layer), and the
    caller's cache is left as it was."""
    cfg = _cfg(name)
    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, batch=3, seq_len=16)
    cache["ssm"] = jax.random.normal(jax.random.key(1), cache["ssm"].shape)
    before = np.asarray(cache["ssm"]).copy()
    in_place = []

    def spying(donated):
        def spy(buf, *args):
            ptr = buf.unsafe_buffer_pointer()
            out = donated(buf, *args)
            new = out[0] if isinstance(out, tuple) else out
            in_place.append(new.unsafe_buffer_pointer() == ptr)
            return out
        return spy

    for fn, donated in list(M2._DONATED.items()):
        monkeypatch.setitem(M2._DONATED, fn, spying(donated))
    with M.eager_layers():
        _, new = M.decode_step(params, cache, jnp.zeros((3, 1), jnp.int32),
                               cfg)
    assert in_place == [True] * (2 * cfg.num_layers)   # ssm and conv
    assert not cache["ssm"].is_deleted() and not cache["conv"].is_deleted()
    np.testing.assert_array_equal(np.asarray(cache["ssm"]), before)
    assert not np.array_equal(np.asarray(new["ssm"]), before)
