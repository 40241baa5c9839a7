"""Reference scores for a set of pool lines, in a child held to the CPU.

    JAX_PLATFORMS=cpu python benchmark/lib/refcheck.py <request.json>

The request names the repo, the configuration's file, the detector's
checkpoint directory (written by ``POST /admin/checkpoint`` after the drain)
and the lines to score. The child reads the fitted float32 parameters and the
fitted threshold from the checkpoint, turns each line into token ids with the
program's parser and tokenizer (the tokenizer is not under test), scores the
tokens with ``benchmark/reference/<model>.py`` and writes
``{"threshold": ..., "scores": {logID: score}}`` beside the request.

It runs after the window has closed and the detector has shut down, so it is
part of neither ``setup_s`` nor the window, and the chip's memory reading
stays the program's.
"""
from __future__ import annotations

import importlib
import json
import os
import sys


def tokens_of(config: dict, lines: list, work: str):
    import numpy as np

    from detectmateservice_tpu.library.parsers.template_matcher import (
        MatcherParser)
    from detectmateservice_tpu.models.tokenizer import HashTokenizer
    from detectmateservice_tpu.schemas import LogSchema, ParserSchema

    templates = os.path.join(work, "refcheck_templates.txt")
    with open(templates, "w", encoding="utf-8") as fh:
        fh.write(config["traffic_source"]["template"] + "\n")
    component = json.loads(json.dumps(config["stages"]["parser"]["component"]))
    for block in component["parsers"].values():
        block.setdefault("params", {})["path_templates"] = templates
    parser = MatcherParser(config=component)
    (scorer,) = config["stages"]["detector"]["component"]["detectors"].values()
    tokenizer = HashTokenizer(vocab_size=scorer.get("vocab_size", 32768),
                              seq_len=scorer["seq_len"])
    out = np.zeros((len(lines), scorer["seq_len"]), np.int32)
    for row, item in enumerate(lines):
        parsed = ParserSchema.from_bytes(parser.process(LogSchema(
            logID=item["id"], logSource="bench", log=item["log"]).serialize()))
        out[row] = tokenizer.encode_parsed(
            parsed.get("template") or "", list(parsed["variables"]),
            dict(parsed["logFormatVariables"]))
    return out


def load_checkpoint(directory: str):
    """The float32 parameter tree and the detector's state, read with orbax
    alone (no template from the program), as numpy arrays: the checkpoint
    names the chip's devices, which this child does not have."""
    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    with open(os.path.join(directory, "meta.json"), encoding="utf-8") as fh:
        meta = json.load(fh)
    path = os.path.join(os.path.abspath(directory),
                        f"params.{meta['data_nonce']}")
    reader = ocp.PyTreeCheckpointer()
    shape = reader.metadata(path)
    shape = getattr(shape, "item_metadata", shape)
    shape = getattr(shape, "tree", shape)
    as_numpy = jax.tree_util.tree_map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), shape)
    tree = reader.restore(
        path, args=ocp.args.PyTreeRestore(restore_args=as_numpy))
    return tree, meta


def main(request_path: str) -> int:
    with open(request_path, encoding="utf-8") as fh:
        request = json.load(fh)
    sys.path.insert(0, request["repo"])
    with open(request["config_file"], encoding="utf-8") as fh:
        config = json.load(fh)
    (scorer,) = config["stages"]["detector"]["component"]["detectors"].values()
    reference = importlib.import_module(
        f"benchmark.reference.{scorer['model']}")
    work = os.path.dirname(os.path.abspath(request_path))
    params, meta = load_checkpoint(request["checkpoint_dir"])
    tokens = tokens_of(config, request["lines"], work)
    scores = reference.score(params, tokens, scorer)
    out = {"threshold": meta["threshold"],
           "fitted": bool(meta.get("fitted")),
           "scores": {item["id"]: float(s) for item, s
                      in zip(request["lines"], scores)}}
    with open(request["out"], "w", encoding="utf-8") as fh:
        json.dump(out, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
