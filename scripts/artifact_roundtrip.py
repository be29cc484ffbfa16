"""CI guard: export a tiny spec-v2 artifact and round-trip it end to end.

Catches export/runtime drift that unit tests mock away: the *serialized*
prefill + decode graphs must load in the model-code-free runtime and drive
``repro.api.ArtifactBackend``.  The decode-graph path and the legacy
full-graph client loop are compiled separately, so each is held to the
float32 reference logits under the injected uniforms
(``repro.core.parity``); the decode-graph stream must equal its generate.

Run:  PYTHONPATH=src python scripts/artifact_roundtrip.py
"""
import sys
import tempfile

import jax
import numpy as np

from repro.api import Client
from repro.configs import get_config
from repro.core import init_delphi
from repro.core.parity import check_trajectory, logit_tolerance
from repro.sdk import InferenceSession, export_model, verify_checksums


def main() -> int:
    # same constants as the tests/test_api.py parity fixture
    cfg = get_config("delphi-2m", reduced=True).replace(
        dtype="float32", vocab_size=96, max_seq_len=48)
    params = init_delphi(cfg, jax.random.PRNGKey(7))
    d = tempfile.mkdtemp(prefix="ci_artifact_")
    export_model(params, cfg, d)
    verify_checksums(d, strict=True)

    toks, ages = [3, 10, 20], [0.0, 15.0, 28.0]
    max_new = 6
    u = np.random.default_rng(42).uniform(
        size=(max_new, cfg.vocab_size)).astype(np.float32)

    client = Client.from_artifact(d)
    assert client.backend.use_decode_graph, "v2 artifact must ship decode"
    res = client.generate(tokens=toks, ages=ages, max_new=max_new,
                          uniforms=u, max_age=1e9)
    legacy = InferenceSession(d).generate_trajectory(
        toks, ages, max_new=max_new, uniforms=u, max_age=1e9)
    for r_toks, r_ages in ((res.tokens, res.ages),
                           (legacy["tokens"], legacy["ages"])):
        assert len(r_toks) > 0
        check_trajectory(params, cfg, toks, ages, r_toks, r_ages, u,
                         tol=logit_tolerance(cfg), max_new=max_new,
                         max_age=1e9)
    streamed = [e.token for e in client.stream(
        tokens=toks, ages=ages, max_new=max_new, uniforms=u, max_age=1e9)]
    assert streamed == res.tokens
    print(f"OK artifact round-trip: decode-graph generate/stream "
          f"({len(res.tokens)} events) and the full-graph loop follow the "
          f"fp32 reference")
    return 0


if __name__ == "__main__":
    sys.exit(main())
