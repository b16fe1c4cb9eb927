"""``python -m paddle_tpu.serving.host`` — one standalone serving host.

Stands up a warm ``DecodeServer`` (and optionally a one-shot ``Server``
over the same model's logits) behind a ``transport.BackendServer``
listener, so a router in another process — or on another machine —
fronts it through ``RemoteBackend``. The launcher spawns one of these
per TPU host.

Lifecycle contract:

- On startup the model is built deterministically (``--seed``), weights
  optionally cold-started from a committed training checkpoint
  (``--checkpoint`` → ``resilience.load_for_serving``), every decode
  executable is pre-compiled (``--warmup``, default on), and only THEN
  does the listener open — a host that accepts traffic is a warm host,
  which is what keeps router-side failover compile-free.
- The bound address is advertised three ways: the ``READY host:port``
  line on stdout, an optional ``--port-file`` (written atomically —
  spawners should poll for it), and the hello handshake every client
  performs (which also carries the bucket config, so the router can
  validate the shared-bucket invariant without an extra round-trip).
- SIGTERM (and SIGINT) means drain-then-exit: stop admitting wire
  requests, finish every in-flight stream and one-shot, close the
  servers, exit 0. SIGKILL is the crash case the router's failover
  drills cover.

Example::

    python -m paddle_tpu.serving.host --port 0 --model gpt2-tiny \\
        --seed 0 --max-slots 4 --page-len 4 --max-context 32 \\
        --prefill-buckets 32 --port-file /tmp/host0.port
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import threading


def _csv_ints(text):
    return [int(t) for t in str(text).split(",") if t.strip()]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving.host",
        description="Standalone serving host (decode + optional "
                    "one-shot) behind the wire transport.")
    p.add_argument("--host", default="127.0.0.1",
                   help="bind address (default 127.0.0.1)")
    p.add_argument("--port", type=int, default=0,
                   help="bind port; 0 = ephemeral (advertised via "
                        "READY line / --port-file)")
    p.add_argument("--backend-id", default=None,
                   help="advertised host id (default host<pid>)")
    p.add_argument("--model", default="gpt2-tiny",
                   choices=("gpt2-tiny", "llama-tiny"),
                   help="which tiny reference model to serve")
    p.add_argument("--num-layers", type=int, default=None,
                   help="override the model's layer count (smaller = "
                        "faster startup in drills)")
    p.add_argument("--seed", type=int, default=0,
                   help="paddle.seed before model construction — every "
                        "host of one fleet MUST use the same seed so "
                        "failover is bitwise-identical")
    p.add_argument("--checkpoint", default=None,
                   help="cold-start weights from this committed "
                        "checkpoint root (or step dir) via "
                        "resilience.load_for_serving")
    p.add_argument("--max-slots", type=int, default=4)
    p.add_argument("--page-len", type=int, default=4)
    p.add_argument("--max-context", type=int, default=32)
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--prefill-buckets", type=_csv_ints, default=None,
                   help="comma-separated prompt buckets (default pow2)")
    p.add_argument("--batch-buckets", type=_csv_ints, default=None,
                   help="comma-separated decode batch buckets")
    p.add_argument("--admission", default="worst_case",
                   choices=("worst_case", "prefill"))
    p.add_argument("--max-queue-size", type=int, default=128)
    p.add_argument("--oneshot", action="store_true",
                   help="also serve one-shot logits requests through a "
                        "serving.Server over the same model")
    p.add_argument("--oneshot-seq-buckets", type=_csv_ints, default=None,
                   help="seq buckets for the one-shot server (must "
                        "match across the fleet)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip pre-compiling the decode executables "
                        "(NOT recommended: failover onto a cold host "
                        "compiles mid-outage)")
    p.add_argument("--port-file", default=None,
                   help="write 'host:port' here (atomically) once "
                        "serving")
    p.add_argument("--drain-timeout-s", type=float, default=30.0,
                   help="bound on the SIGTERM drain before exit")
    p.add_argument("--trace-dir", default=None,
                   help="enable the flight recorder and background-"
                        "flush this host's chrome trace to "
                        "<dir>/<backend-id>.trace.json (the file a "
                        "SIGKILLed host leaves behind for "
                        "tools/trace_merge.py); defaults to "
                        "$PADDLE_TRACE_DIR when set")
    return p


def _build_model(args):
    import paddle_tpu as paddle
    paddle.seed(args.seed)
    if args.model == "gpt2-tiny":
        from paddle_tpu.models import GPTForCausalLM, gpt2_tiny
        cfg = gpt2_tiny()
        if args.num_layers is not None:
            cfg.num_layers = args.num_layers
        model = GPTForCausalLM(cfg)
    else:
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        cfg = llama_tiny()
        if args.num_layers is not None:
            cfg.num_layers = args.num_layers
        model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    backend_id = args.backend_id or f"host{os.getpid()}"

    # heavyweight imports AFTER arg parsing so --help stays instant
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.profiler import tracing
    from paddle_tpu.serving import Server, decode
    from paddle_tpu.serving.transport import BackendServer

    # flight recorder BEFORE model build so warmup compiles are traced;
    # the background writer is what makes SIGKILL leave a trace behind
    trace_dir = args.trace_dir or os.environ.get("PADDLE_TRACE_DIR")
    if trace_dir:
        tracing.enable_tracing()
        tracing.set_trace_metadata(backend_id=backend_id, role="host")
        tracing.start_trace_writer(
            os.path.join(trace_dir, f"{backend_id}.trace.json"))

    # warmup compiles every bucket's executable: keep them across restarts
    enable_compile_cache()
    model = _build_model(args)
    if args.checkpoint:
        from paddle_tpu.distributed.resilience import load_for_serving
        step = load_for_serving(args.checkpoint, model)
        print(f"loaded committed checkpoint step {step} from "
              f"{args.checkpoint}", flush=True)

    dsrv = decode.DecodeServer(
        model, max_slots=args.max_slots, page_len=args.page_len,
        max_context=args.max_context,
        max_new_tokens=args.max_new_tokens,
        prefill_buckets=args.prefill_buckets,
        batch_buckets=args.batch_buckets, admission=args.admission,
        max_queue_size=args.max_queue_size,
        name=f"{backend_id}_decode")
    oneshot = None
    if args.oneshot:
        oneshot = Server(model, seq_buckets=args.oneshot_seq_buckets,
                         max_queue_size=args.max_queue_size,
                         name=f"{backend_id}_oneshot")
    if not args.no_warmup:
        n = dsrv.warmup()
        print(f"warmup compiled {n} decode executables", flush=True)

    # handlers BEFORE the listener opens: a spawner may SIGTERM the
    # instant it reads READY, and the drain contract must already hold
    stop = threading.Event()

    def _on_signal(signum, frame):
        del frame
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    # warm first, listen second: a host that accepts traffic is a warm
    # host (router failover must land on compiled executables)
    bs = BackendServer(backend_id=backend_id, server=oneshot,
                       decode_server=dsrv, host=args.host,
                       port=args.port, owns_servers=True)
    host, port = bs.address
    if args.port_file:
        tmp = f"{args.port_file}.tmp{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(f"{host}:{port}")
        os.replace(tmp, args.port_file)
    print(f"READY {host}:{port}", flush=True)

    while not stop.wait(0.2):
        pass

    # drain-then-exit: stop admitting, finish in-flight work, close
    print("draining (SIGTERM)", flush=True)
    drained = bs.shutdown(drain=True, timeout=args.drain_timeout_s)
    if trace_dir:
        # final flush: the clean-exit counterpart of the SIGKILL case
        tracing.stop_trace_writer()
        tracing.export_trace(
            os.path.join(trace_dir, f"{backend_id}.trace.json"))
    print(f"drained={drained} exiting", flush=True)
    return 0 if drained else 1


if __name__ == "__main__":
    sys.exit(main())
