"""``rafiki-tpu`` command-line entry point.

Replaces the reference's ``scripts/start.sh``/``stop.sh`` + per-service
Docker entrypoints (SURVEY.md §2 "Deployment") with one multi-command CLI.
Service subcommands are registered as their layers land.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="rafiki-tpu",
        description="TPU-native AutoML train-and-serve framework")
    sub = parser.add_subparsers(dest="cmd")

    sub.add_parser("version", help="print version")

    p_tune = sub.add_parser(
        "tune", help="local tuning loop over a zoo template (dev use)")
    p_tune.add_argument("template", help="zoo template name, e.g. JaxFeedForward")
    p_tune.add_argument("train_dataset")
    p_tune.add_argument("val_dataset")
    p_tune.add_argument("--trials", type=int, default=5)
    p_tune.add_argument("--advisor", default="auto")
    p_tune.add_argument("--profile", metavar="DIR", default=None,
                        help="write a jax.profiler trace per trial to DIR")

    p_bpe = sub.add_parser(
        "bpe-train",
        help="train a byte-level BPE tokenizer artifact from a corpus "
             "(for LlamaLoRA's tokenizer_path knob)")
    p_bpe.add_argument("corpus", help="UTF-8 text file (or .jsonl with "
                                      "'text' fields) to learn merges from")
    p_bpe.add_argument("out", help="artifact path, e.g. bpe.json")
    p_bpe.add_argument("--vocab", type=int, default=8192,
                       help="target vocab size (specials + 256 bytes + "
                            "merges)")

    p_doc = sub.add_parser(
        "doctor",
        help="check the environment (backend, devices, native "
             "artifacts, compile cache) and print a health report; "
             "with --workdir, audit a stack workdir instead (MetaStore "
             "rows vs live pids vs slots vs obs ports — drift report)")
    p_doc.add_argument("--workdir", default=None,
                       help="stack workdir to audit (read-only; safe "
                            "against a live stack)")
    p_doc.add_argument("--json", action="store_true", dest="as_json",
                       help="emit the audit as JSON (with --workdir)")

    p_backup = sub.add_parser(
        "backup",
        help="snapshot a stack's MetaStore (SQLite online backup; "
             "consistent under a live admin) — run before risky ops")
    p_backup.add_argument("out", help="destination file for the snapshot")
    p_backup.add_argument("--workdir", default="./rafiki_stack",
                          help="stack workdir holding meta.db")

    p_lint = sub.add_parser(
        "lint",
        help="run the JAX/concurrency-aware static analyzer over "
             "source paths (exit 0 = clean; see docs/linting.md)")
    from .analysis.cli import add_lint_arguments

    add_lint_arguments(p_lint)

    _register_service_commands(sub)

    args = parser.parse_args(argv)
    if args.cmd is None:
        parser.print_help()
        return 2
    if args.cmd == "lint":
        # pure AST analysis — no jax, no backend, no platform env;
        # keeping it import-light makes the CI gate start instantly
        from .analysis.cli import run_lint

        return run_lint(args)
    if args.cmd == "doctor" and args.workdir:
        # workdir drift audit: pure /proc + sqlite reads, no jax, no
        # backend — must work on a box whose accelerator is wedged
        # (that is exactly when operators reach for it)
        return _doctor_workdir(args.workdir, args.as_json)
    if args.cmd == "backup":
        import json as _json

        from .store.meta_store import MetaStore

        db = f"{args.workdir}/meta.db"
        import os.path

        if not os.path.exists(db):
            print(f"no MetaStore at {db}", file=sys.stderr)
            return 1
        # read-only open: the backup tool must never migrate or touch
        # the live store it is snapshotting
        out = MetaStore(db, read_only=True).backup(args.out)
        print(_json.dumps({"ok": True, **out}))
        return 0
    # the shared compile cache, before any backend initializes
    from .utils.platform import apply_platform_env

    apply_platform_env()
    if args.cmd == "version":
        from . import __version__

        print(__version__)
        return 0
    if args.cmd == "tune":
        from .model import tune_model
        from .models import get_model_template

        result = tune_model(get_model_template(args.template),
                            args.train_dataset, args.val_dataset,
                            total_trials=args.trials,
                            advisor_type=args.advisor,
                            profile_dir=args.profile)
        print(f"best_score={result.best_score:.4f} "
              f"best_knobs={result.best_knobs}")
        return 0
    if args.cmd == "bpe-train":
        import json

        from .data.bpe import ByteBPETokenizer

        is_jsonl = args.corpus.endswith(".jsonl")

        def lines():
            # format by EXTENSION, not per-line sniffing: a plain-text
            # corpus may legitimately contain JSON-looking lines, and a
            # .jsonl metadata row must not leak '{"'-style punctuation
            # into the merge table
            with open(args.corpus, encoding="utf-8") as f:
                for line in f:
                    if not is_jsonl:
                        yield line
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    text = rec.get("text") if isinstance(rec, dict) \
                        else None
                    if isinstance(text, str):  # skip metadata/null rows
                        yield text

        tok = ByteBPETokenizer.train(lines(), vocab_size=args.vocab)
        tok.save(args.out)
        print(f"vocab_size={tok.vocab_size} merges={len(tok.merges)} "
              f"-> {args.out}")
        return 0
    if args.cmd == "doctor":
        return _doctor()
    return _run_service_command(args)


def _doctor_workdir(workdir: str, as_json: bool) -> int:
    """Drift audit over a stack workdir; exit 0 iff zero drift."""
    import json as _json

    from .admin.doctor import audit_workdir, render_text

    report = audit_workdir(workdir)
    if as_json:
        print(_json.dumps(report, indent=2))
    else:
        print(render_text(report))
    return 0 if report["ok"] else 1


def _doctor() -> int:
    """Operator health report: every row is a check with a pass/fail
    mark; exit 0 iff all load-bearing checks pass. Never claims the
    accelerator beyond a tiny matmul (one probe)."""
    ok = True

    def row(good: bool, label: str, detail: str = "",
            fatal: bool = True) -> None:
        nonlocal ok
        mark = "ok " if good else ("FAIL" if fatal else "warn")
        print(f"[{mark}] {label}" + (f": {detail}" if detail else ""))
        if fatal and not good:
            ok = False

    from . import __version__

    row(True, "rafiki-tpu", __version__)
    try:
        import jax

        backend = jax.default_backend()
        devs = jax.devices()
        row(True, "jax backend", f"{backend}, {len(devs)} device(s)")
        import time

        import jax.numpy as jnp

        t0 = time.perf_counter()
        x = jnp.ones((256, 256), jnp.bfloat16)
        (x @ x).block_until_ready()
        row(True, "device matmul",
            f"bf16 256x256 in {time.perf_counter() - t0:.2f}s "
            "(first call includes compile)")
    except Exception as e:  # noqa: BLE001 — the report IS the product
        row(False, "jax backend", str(e))
    try:
        from .native.client import ensure_built

        row(True, "native kv server", str(ensure_built()))
        row(True, "native bpe encoder",
            str(ensure_built(target="librbpe.so")))
    except Exception as e:  # noqa: BLE001
        row(False, "native build", str(e), fatal=False)
    try:
        from .data.bpe import ByteBPETokenizer

        tok = ByteBPETokenizer.train(["doctor check"] * 4,
                                     vocab_size=270)
        row(tok.decode(tok.encode_ids("doctor")) == "doctor",
            "bpe round-trip",
            "native" if tok._native is not None else "python fallback")
    except Exception as e:  # noqa: BLE001
        row(False, "bpe round-trip", str(e))
    import os

    from .utils.platform import compile_cache_path

    path = compile_cache_path()
    # the dir may not exist yet — apply_platform_env's makedirs creates
    # the whole chain, so test W_OK at the nearest EXISTING ancestor
    # rather than warning spuriously
    probe = path  # start at the path ITSELF: it may be a plain file
    blocked = False  # a FILE at any level blocks makedirs
    while probe and not os.path.isdir(probe):
        if os.path.exists(probe):
            blocked = True
            break
        parent = os.path.dirname(probe)
        if parent == probe:
            break
        probe = parent
    row(not blocked and os.access(probe or ".", os.W_OK),
        "compile cache", path, fatal=False)
    pg = os.environ.get("RAFIKI_PG_URL", "")
    if not pg:
        row(True, "postgres",
            "not configured (RAFIKI_PG_URL unset; sqlite is the default "
            "MetaStore backing)")
    else:
        from urllib.parse import urlsplit

        def redact(text: str) -> str:
            # structural redaction (not a regex over the URL — an
            # unencoded '@' or '/' inside a password defeats those):
            # every userinfo fragment is scrubbed from any output,
            # including driver exception text that may echo the URL
            try:
                netloc = urlsplit(pg).netloc
            except ValueError:
                netloc = ""
            userinfo, _, _hostport = netloc.rpartition("@")
            if userinfo:
                text = text.replace(userinfo, "***")
                pw = userinfo.partition(":")[2]
                if pw:
                    text = text.replace(pw, "***")
            return text

        shown = redact(pg)
        try:
            from .store.db import PostgresAdapter

            a = PostgresAdapter(pg)
            conn = a.connect()
            try:
                one = a.execute(conn, "SELECT 1 AS ok").fetchone()
            finally:
                a.close(conn)
            row(bool(one and one.get("ok") == 1), "postgres", shown,
                fatal=False)
        except Exception as e:  # noqa: BLE001 — the report IS the product
            row(False, "postgres", redact(f"{shown}: {e}"), fatal=False)
    print("all checks passed" if ok else "SOME CHECKS FAILED")
    return 0 if ok else 1


def _register_service_commands(sub: argparse._SubParsersAction) -> None:
    p = sub.add_parser("stack", help="manage the full local service stack")
    p.add_argument("action", choices=["start", "stop", "status"])
    p.add_argument("--workdir", default="./rafiki_stack")
    p.add_argument("--port", type=int, default=3000,
                   help="admin REST port")
    p.add_argument("--workers", type=int, default=1,
                   help="train workers per job when the budget names no "
                        "WORKER_COUNT/GPU_COUNT")
    p.add_argument("--slot-size", dest="slot_size", type=int, default=1,
                   help="devices per trial slot (ICI-contiguous sub-mesh "
                        "size; e.g. 2 on 8 devices -> 4 slots)")
    p.add_argument("--cold", action="store_true",
                   help="start: kill every recorded survivor instead of "
                        "re-adopting it (clean-slate boot for when the "
                        "previous stack's state is not to be trusted)")


def _run_service_command(args: argparse.Namespace) -> int:
    if args.cmd == "stack":
        try:
            from .admin.stack import stack_command
        except ImportError:
            print("the service stack is not available in this build",
                  file=sys.stderr)
            return 2
        return stack_command(args)
    print(f"unknown command {args.cmd!r}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
