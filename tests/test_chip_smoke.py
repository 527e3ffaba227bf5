"""Rehearsal of ``chip_smoke.py`` on the CPU at reduced widths.

Runs the smoke's serving function -- engine on the production path behind
the HTTP server, mixed-plan traffic, one streamed request, and the
kernel-path vs kernels-off logit comparison -- on ``olmoe-1b-7b``
``.reduced()``, so every PR exercises the path the chip run takes.  The
TPU guard itself is checked to refuse the CPU.
"""

import jax
import pytest

import chip_smoke
from repro.configs import get_config
from repro.kernels import ops


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("olmoe-1b-7b").reduced()
    return chip_smoke.run_smoke(
        cfg, seed=0, max_batch=4, max_len=128, page_size=16, chunk=16,
        n_requests=4, prompt_lens=(8, 40), max_new=6, plan=(2, 1, 1, 2),
        log=lambda *_: None)


def test_http_answers(smoke):
    eng, facts = smoke
    res = facts["results"]
    assert len(res) == 4
    assert [r["served_plan"] for r in res] == ["base", "lexi"] * 2
    assert all(r["finished_reason"] == "length" and len(r["tokens"]) == 6
               for r in res)
    assert res[1]["text"]               # the streamed one carried text
    assert facts["stats"]["decode_tokens"] > 0
    assert facts["stats"]["mixed_plan_steps"] > 0
    assert eng.idle()


def test_logit_check_passes_and_discriminates(smoke):
    eng, facts = smoke
    parts = facts["parts"]
    n = eng.runner.base_cfg.num_layers
    assert [(p["layer"], p["part"], p["wrong"]) for p in parts] == [
        (layer, part, wrong) for layer in range(n)
        for part, wrong in (("attention", "pages"), ("experts", "experts"))]
    for p in parts:
        assert p["err"] <= p["tol"]
        assert p["err_wrong"] > 10 * p["tol"]


def test_weights_held_once(smoke):
    eng, facts = smoke
    # served in the split layout as built: no regrouped second copy
    n_groups = len(eng.runner.params["stack"]["groups"])
    assert n_groups == eng.runner.base_cfg.num_layers
    assert facts["weight_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(eng.runner.params))


def test_graph_check_catches_the_interpret_route(smoke):
    # on the CPU every kernel takes its interpret or jnp route, so the
    # compiled graphs call no Mosaic kernel and the check must say so
    eng, facts = smoke
    with pytest.raises(RuntimeError, match="calls no Mosaic kernel"):
        chip_smoke.assert_kernels_in_graphs(eng, facts["step"],
                                            chunk=eng.prefill_chunk,
                                            log=lambda *_: None)


def test_guard_refuses_cpu():
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.main([])


def test_interpret_only_on_cpu(monkeypatch):
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="neither"):
        ops._interpret()
