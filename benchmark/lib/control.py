"""The control of a run's ``correct``: the reference put in the program's
place, one precision below the configuration's, in a child held to the CPU.

    JAX_PLATFORMS=cpu python benchmark/lib/control.py <request.json> <dtype>

Builder's tool (``benchmark/sweep.py --control``), never part of a check.
The request is the one the run wrote for ``refcheck.py``, whose answer (the
plain reference's scores) lies beside it. Every line the lowered reference
would alert on (its score clears the fitted threshold) is judged against the
plain reference as an alert of the program is (score gaps, missing and false
alerts), and each number is printed beside the configuration's limit. A control that stays under every limit is
no control, and the exit code says so.
"""
from __future__ import annotations

import importlib
import json
import os
import sys


def main(request_path: str, dtype: str) -> int:
    with open(request_path, encoding="utf-8") as fh:
        request = json.load(fh)
    sys.path.insert(0, request["repo"])
    import jax.numpy as jnp

    from benchmark.lib import refcheck, verdict

    with open(request["config_file"], encoding="utf-8") as fh:
        config = json.load(fh)
    with open(request["out"], encoding="utf-8") as fh:
        plain = json.load(fh)
    (scorer,) = config["stages"]["detector"]["component"]["detectors"].values()
    reference = importlib.import_module(
        f"benchmark.reference.{scorer['model']}")
    work = os.path.dirname(os.path.abspath(request_path))
    params, _ = refcheck.load_checkpoint(request["checkpoint_dir"])
    tokens = refcheck.tokens_of(config, request["lines"], work)
    lowered = reference.score(params, tokens, scorer,
                              lower=getattr(jnp, dtype))
    threshold = float(plain["threshold"])
    alerts = {item["id"]: [float(low)]
              for item, low in zip(request["lines"], lowered)
              if low > threshold}
    judged = verdict.judge(alerts, {i: 1 for i in plain["scores"]},
                           plain["scores"], threshold, config["check"], [])
    fails = False
    for name, value, limit in judged["numbers"]:
        fails |= value > limit
        print(f"control {dtype}: {name} = {value:g} (limit {limit:g}) "
              f"{'fails' if value > limit else 'passes'}", flush=True)
    print(f"control {dtype}: over {len(alerts)} lines it alerts on — "
          f"{'not correct, as a control has to be' if fails else 'NO CONTROL: it passes every limit'}",
          flush=True)
    return 0 if fails else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
