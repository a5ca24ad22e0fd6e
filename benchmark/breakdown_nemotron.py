"""``breakdown.py`` with the Nemotron cell's scopes named.

    python3 benchmark/breakdown_nemotron.py nemotron-3-super-120b-a12b.serve.agentic [--program step]

The same JSON object as ``breakdown.py`` (which this leaves as it is), the
device's seconds by scope counted under the Mamba mixers' (and their parts),
attention's, the expert layer's and the head's scopes. It measures
nothing."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import breakdown  # noqa: E402

SCOPES = ("ssm_conv", "ssm_scan", "ssm_update", "ssm_norm", "mamba", "kv_write", "attn_gqa",
          "moe_latent", "shared_expert", "moe_router", "moe_dispatch", "moe_experts",
          "moe_combine", "lm_head", "sample")

if __name__ == "__main__":
    breakdown.SERVE_SCOPES = SCOPES
    sys.exit(breakdown.main())
