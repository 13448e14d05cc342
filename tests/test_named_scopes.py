"""Named scopes on the parts of a MoE decode step: the compiled HLO
carries each part's scope in its op metadata, so the device ops of a
profiler trace can be put down to the router, the slot-bank gather,
dispatch, the expert FFN, combine, attention and the KV write. The
scopes are metadata only: they change no logit."""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced
from repro.models import get_model

SCOPES = ("router", "slot_bank_gather", "dispatch", "expert_ffn", "combine",
          "attention", "kv_write")


def compiled_decode(api, args):
    """(compiled HLO text, logits) of one decode step, traced afresh."""
    fn = jax.jit(lambda *a: api.decode(*a))
    logits, _ = fn(*args)
    return fn.lower(*args).compile().as_text(), np.asarray(logits)


@pytest.fixture(scope="module")
def decode_step():
    cfg = reduced("mixtral_8x7b", cap_factor=4.0)
    api = get_model(cfg, num_aw=2, num_ew=2, tarragon=True)
    args = (api.init_params(jax.random.PRNGKey(0)),
            jnp.array([3, 5, 7, 9], jnp.int32),
            jnp.array([4, 0, 9, -1], jnp.int32),
            api.init_cache(4, 32), api.init_route_state())
    return api, args, *compiled_decode(api, args)


@pytest.mark.parametrize("scope", SCOPES)
def test_compiled_decode_step_names_the_scope(decode_step, scope):
    _, _, hlo, _ = decode_step
    assert f"/{scope}/" in hlo


def test_scopes_change_no_logit(decode_step, monkeypatch):
    api, args, _, logits = decode_step
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    hlo, plain = compiled_decode(api, args)
    assert not any(f"/{scope}/" in hlo for scope in SCOPES)
    np.testing.assert_array_equal(plain, logits)


def test_the_qk_norm_has_its_scope():
    """A model with a per-head q/k norm (Qwen3's) names it ``qk_norm``;
    one without has no such op."""
    for qk_norm in (True, False):
        cfg = dataclasses.replace(reduced("mixtral_8x7b", cap_factor=4.0),
                                  qk_norm=qk_norm)
        api = get_model(cfg, num_aw=2, num_ew=2, tarragon=True)
        args = (api.init_params(jax.random.PRNGKey(0)),
                jnp.array([3, 5, 7, 9], jnp.int32),
                jnp.array([4, 0, 9, -1], jnp.int32),
                api.init_cache(4, 32), api.init_route_state())
        hlo, _ = compiled_decode(api, args)
        assert ("/qk_norm/" in hlo) == qk_norm
