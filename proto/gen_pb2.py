"""Regenerate crowdllama_tpu/core/llama_v1_pb2.py WITHOUT protoc.

The container has no protoc, so the pb2 module is maintained by editing the
serialized FileDescriptorProto embedded in the generated file: parse the
current bytes with google.protobuf.descriptor_pb2, apply schema edits in
Python, re-serialize, and emit a fresh generated module with recomputed
_serialized_start/_end offsets (located by substring search — each message's
serialized DescriptorProto appears verbatim inside the file bytes).

Run from the repo root:  python proto/gen_pb2.py

The script is idempotent: edits are expressed as "ensure field/message
exists", so re-running against an already-regenerated file is a no-op.
Keep proto/llama_v1.proto in sync by hand — it is documentation; this file
is the source of truth for the bytes on the wire.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

from google.protobuf import descriptor_pb2

REPO = Path(__file__).resolve().parent.parent
PB2 = REPO / "crowdllama_tpu" / "core" / "llama_v1_pb2.py"

F = descriptor_pb2.FieldDescriptorProto
OPT, REP = F.LABEL_OPTIONAL, F.LABEL_REPEATED
STR, BYTES, I32, BOOL = (F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_INT32,
                         F.TYPE_BOOL)
U64 = F.TYPE_UINT64
MSG = F.TYPE_MESSAGE


def _field(name, number, ftype, label=OPT, type_name="", oneof_index=None):
    f = F(name=name, number=number, label=label, type=ftype)
    if type_name:
        f.type_name = type_name
    if oneof_index is not None:
        f.oneof_index = oneof_index
    return f


def _ensure_field(msg, field):
    if any(f.name == field.name for f in msg.field):
        return False
    # Keep fields sorted by number so the serialized descriptor (and
    # therefore the offsets below) stay deterministic.
    msg.field.append(field)
    msg.field.sort(key=lambda f: f.number)
    return True


def _ensure_message(fdp, desc, before="BaseMessage"):
    if any(m.name == desc.name for m in fdp.message_type):
        return False
    idx = next((i for i, m in enumerate(fdp.message_type)
                if m.name == before), len(fdp.message_type))
    fdp.message_type.insert(idx, desc)
    return True


def extract_serialized(src: str) -> bytes:
    m = re.search(r"AddSerializedFile\((b'.*?')\)", src, re.S)
    if not m:
        raise SystemExit("could not find AddSerializedFile(...) in pb2")
    return eval(m.group(1))  # noqa: S307 - trusted repo file, bytes literal


def apply_schema_edits(fdp: descriptor_pb2.FileDescriptorProto) -> None:
    """PR 5: peer-to-peer paged-KV shipping messages.
    PR 6: live request migration (graceful drain).
    PR 7: replicated gateway plane (gossip LWW map + tenant digests)."""
    # GenerateRequest.kv_donor: peer id of a worker believed to hold this
    # conversation's prefix KV hot (gateway affinity memory).  Proto3
    # back-compat: absent == "" == no hint.
    (gen_req,) = [m for m in fdp.message_type if m.name == "GenerateRequest"]
    _ensure_field(gen_req, _field("kv_donor", 12, STR))
    # GenerateRequest.migrate: this request is the gateway's re-route of a
    # stream a draining worker handed back (docs/ROBUSTNESS.md drain
    # machine).  The serving worker treats the kv_donor fetch as mandatory
    # recovery (bypasses the kv-ship opt-in + min-token gates) and accounts
    # recomputed prefill under replayed_prefill_tokens.  Absent == false.
    _ensure_field(gen_req, _field("migrate", 13, BOOL))

    kv_fetch = descriptor_pb2.DescriptorProto(name="KvFetchRequest")
    _ensure_field(kv_fetch, _field("model", 1, STR))
    _ensure_field(kv_fetch, _field("chain_hashes", 2, BYTES, REP))
    _ensure_field(kv_fetch, _field("page_size", 3, I32))
    _ensure_message(fdp, kv_fetch)

    kv_pages = descriptor_pb2.DescriptorProto(name="KvPages")
    _ensure_field(kv_pages, _field("model", 1, STR))
    _ensure_field(kv_pages, _field("matched", 2, I32))
    _ensure_field(kv_pages, _field("start", 3, I32))
    _ensure_field(kv_pages, _field("k_pages", 4, BYTES, REP))
    _ensure_field(kv_pages, _field("v_pages", 5, BYTES, REP))
    _ensure_field(kv_pages, _field("k_scales", 6, BYTES, REP))
    _ensure_field(kv_pages, _field("v_scales", 7, BYTES, REP))
    _ensure_field(kv_pages, _field("kv_dtype", 8, STR))
    _ensure_field(kv_pages, _field("done", 9, BOOL))
    _ensure_field(kv_pages, _field("error", 10, STR))
    _ensure_message(fdp, kv_pages)

    # MigrateFrame: a draining worker's mid-stream handoff.  Emitted in
    # place of the terminal GenerateResponse on every in-flight stream when
    # the worker drains; carries the generation state the gateway needs to
    # re-route with fetch-instead-of-recompute (the worker itself stays
    # alive as a KV donor until drain_timeout).
    mig = descriptor_pb2.DescriptorProto(name="MigrateFrame")
    _ensure_field(mig, _field("model", 1, STR))
    _ensure_field(mig, _field("worker_id", 2, STR))
    _ensure_field(mig, _field("delivered_tokens", 3, I32))
    _ensure_field(mig, _field("prompt_tokens", 4, I32))
    _ensure_field(mig, _field("chain_hashes", 5, BYTES, REP))
    _ensure_field(mig, _field("page_size", 6, I32))
    _ensure_field(mig, _field("reason", 7, STR))
    _ensure_message(fdp, mig)

    # Replicated gateway plane (docs/ROBUSTNESS.md "replicated gateway"):
    # versioned LWW entries + per-tenant usage digests exchanged between
    # gateway replicas over the authenticated inference stream protocol.
    gent = descriptor_pb2.DescriptorProto(name="GossipEntry")
    _ensure_field(gent, _field("key", 1, STR))
    _ensure_field(gent, _field("value", 2, STR))
    _ensure_field(gent, _field("version", 3, U64))
    _ensure_field(gent, _field("tombstone", 4, BOOL))
    _ensure_field(gent, _field("origin", 5, STR))
    _ensure_message(fdp, gent)

    tuse = descriptor_pb2.DescriptorProto(name="TenantUsage")
    _ensure_field(tuse, _field("origin", 1, STR))
    _ensure_field(tuse, _field("tenant", 2, STR))
    _ensure_field(tuse, _field("admitted", 3, U64))
    _ensure_field(tuse, _field("version", 4, U64))
    _ensure_message(fdp, tuse)

    gfr = descriptor_pb2.DescriptorProto(name="GossipFrame")
    _ensure_field(gfr, _field("origin", 1, STR))
    _ensure_field(gfr, _field("entries", 2, MSG, REP,
                              type_name=".llama.v1.GossipEntry"))
    _ensure_field(gfr, _field("usage", 3, MSG, REP,
                              type_name=".llama.v1.TenantUsage"))
    _ensure_field(gfr, _field("sync", 4, BOOL))
    _ensure_field(gfr, _field("clock", 5, U64))
    _ensure_message(fdp, gfr)

    # PR 8: swarm-stitched traces (docs/OBSERVABILITY.md collector).  The
    # gateway's collector fans a TraceFetch out to every node a request
    # touched; each answers with its span fragment for that trace_id.
    tfr = descriptor_pb2.DescriptorProto(name="TraceFetch")
    _ensure_field(tfr, _field("trace_id", 1, STR))
    _ensure_message(fdp, tfr)

    # TraceSpans: one node's fragment.  ``payload`` is the node's trace
    # record as JSON (the exact /debug/trace shape — spans with start_us
    # offsets from the node's own clock plus started_at wall time, which
    # the collector aligns per hop); ``found`` distinguishes "no such
    # trace here" from an empty record.
    tsp = descriptor_pb2.DescriptorProto(name="TraceSpans")
    _ensure_field(tsp, _field("trace_id", 1, STR))
    _ensure_field(tsp, _field("node", 2, STR))
    _ensure_field(tsp, _field("payload", 3, BYTES))
    _ensure_field(tsp, _field("found", 4, BOOL))
    _ensure_field(tsp, _field("error", 5, STR))
    _ensure_message(fdp, tsp)

    # PR 13: swarm observatory (docs/OBSERVABILITY.md).  The gateway fans a
    # MetricsFetch out to every worker over the same authenticated stream
    # plane as TraceFetch; each answers with its full Prometheus exposition
    # text, re-exported under a worker label at GET /metrics/cluster.
    mfr = descriptor_pb2.DescriptorProto(name="MetricsFetch")
    _ensure_field(mfr, _field("families", 1, STR, REP))
    _ensure_message(fdp, mfr)

    # MetricsSnapshot: one node's scrape.  ``payload`` is the node's own
    # /metrics exposition text (UTF-8); ``found`` distinguishes "obs plane
    # disabled here" from an empty exposition.
    msn = descriptor_pb2.DescriptorProto(name="MetricsSnapshot")
    _ensure_field(msn, _field("node", 1, STR))
    _ensure_field(msn, _field("payload", 2, BYTES))
    _ensure_field(msn, _field("found", 3, BOOL))
    _ensure_field(msn, _field("error", 4, STR))
    _ensure_message(fdp, msn)

    # PR 20: gateway-side speculative pipeline (docs/SPECULATIVE.md).
    # GenerateRequest.remote_draft: the client (a gateway hosting the
    # distilled draft model) will pace this stream with DraftChunk frames
    # on the same inference stream and expects VerifyResult frames
    # interleaved with the GenerateResponse frames.  Absent == false ==
    # the pre-PR-20 streaming protocol, bit for bit.
    _ensure_field(gen_req, _field("remote_draft", 14, BOOL))

    # DraftChunk: client → worker.  One chunk of speculative draft tokens
    # proposed by the gateway's local draft model, starting at absolute
    # sequence ``position`` (prompt + committed completion tokens).  An
    # EMPTY tokens list is a pure pipeline credit ("ack"): it authorizes
    # one more verify round without proposing anything — the worker-draft
    # pacing mode.
    dch = descriptor_pb2.DescriptorProto(name="DraftChunk")
    _ensure_field(dch, _field("model", 1, STR))
    _ensure_field(dch, _field("chunk_id", 2, U64))
    _ensure_field(dch, _field("position", 3, I32))
    _ensure_field(dch, _field("tokens", 4, I32, REP))
    _ensure_message(fdp, dch)

    # VerifyResult: worker → client.  The outcome of one verify round:
    # how many drafts of ``chunk_id`` were accepted, every token id the
    # round actually emitted (accepted drafts + the model's own token),
    # and the committed absolute position afterwards.  chunk_id 0 is the
    # stream handshake (carries prompt_ids + the first emitted token so
    # the gateway's draft session needs no tokenizer); ``draft_k`` is the
    # worker's preferred drafts-per-chunk (0 = stop drafting, send pure
    # credits) and ``depth_hint`` its max-in-flight window.
    vr = descriptor_pb2.DescriptorProto(name="VerifyResult")
    _ensure_field(vr, _field("chunk_id", 1, U64))
    _ensure_field(vr, _field("position", 2, I32))
    _ensure_field(vr, _field("accepted", 3, I32))
    _ensure_field(vr, _field("tokens", 4, I32, REP))
    _ensure_field(vr, _field("done", 5, BOOL))
    _ensure_field(vr, _field("draft_k", 6, I32))
    _ensure_field(vr, _field("depth_hint", 7, I32))
    _ensure_field(vr, _field("prompt_ids", 8, I32, REP))
    _ensure_message(fdp, vr)

    (base,) = [m for m in fdp.message_type if m.name == "BaseMessage"]
    _ensure_field(base, _field("kv_fetch_request", 7, MSG,
                               type_name=".llama.v1.KvFetchRequest",
                               oneof_index=0))
    _ensure_field(base, _field("kv_pages", 8, MSG,
                               type_name=".llama.v1.KvPages",
                               oneof_index=0))
    _ensure_field(base, _field("migrate_frame", 9, MSG,
                               type_name=".llama.v1.MigrateFrame",
                               oneof_index=0))
    _ensure_field(base, _field("gossip_frame", 10, MSG,
                               type_name=".llama.v1.GossipFrame",
                               oneof_index=0))
    _ensure_field(base, _field("trace_fetch", 11, MSG,
                               type_name=".llama.v1.TraceFetch",
                               oneof_index=0))
    _ensure_field(base, _field("trace_spans", 12, MSG,
                               type_name=".llama.v1.TraceSpans",
                               oneof_index=0))
    _ensure_field(base, _field("metrics_fetch", 13, MSG,
                               type_name=".llama.v1.MetricsFetch",
                               oneof_index=0))
    _ensure_field(base, _field("metrics_snapshot", 14, MSG,
                               type_name=".llama.v1.MetricsSnapshot",
                               oneof_index=0))
    _ensure_field(base, _field("draft_chunk", 15, MSG,
                               type_name=".llama.v1.DraftChunk",
                               oneof_index=0))
    _ensure_field(base, _field("verify_result", 16, MSG,
                               type_name=".llama.v1.VerifyResult",
                               oneof_index=0))


def render(fdp: descriptor_pb2.FileDescriptorProto) -> str:
    data = fdp.SerializeToString()
    offsets = []
    for m in fdp.message_type:
        sub = m.SerializeToString()
        start = data.find(sub)
        if start < 0:
            raise SystemExit(f"serialized {m.name} not found in file bytes")
        offsets.append((m.name.upper(), start, start + len(sub)))
    lit = repr(data)
    if lit.startswith("b\""):  # normalize to single-quoted bytes literal
        lit = "b'" + lit[2:-1].replace("'", "\\'").replace('\\"', '"') + "'"
    lines = [
        "# -*- coding: utf-8 -*-",
        "# Generated by the protocol buffer compiler.  DO NOT EDIT!",
        "# source: llama_v1.proto",
        '"""Generated protocol buffer code."""',
        "from google.protobuf.internal import builder as _builder",
        "from google.protobuf import descriptor as _descriptor",
        "from google.protobuf import descriptor_pool as _descriptor_pool",
        "from google.protobuf import symbol_database as _symbol_database",
        "# @@protoc_insertion_point(imports)",
        "",
        "_sym_db = _symbol_database.Default()",
        "",
        "",
        "from google.protobuf import timestamp_pb2 as "
        "google_dot_protobuf_dot_timestamp__pb2",
        "",
        "",
        f"DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({lit})",
        "",
        "_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())",
        "_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, "
        "'llama_v1_pb2', globals())",
        "if _descriptor._USE_C_DESCRIPTORS == False:",
        "",
        "  DESCRIPTOR._options = None",
    ]
    for name, start, end in offsets:
        lines.append(f"  _{name}._serialized_start={start}")
        lines.append(f"  _{name}._serialized_end={end}")
    lines.append("# @@protoc_insertion_point(module_scope)")
    return "\n".join(lines) + "\n"


def main() -> int:
    src = PB2.read_text()
    fdp = descriptor_pb2.FileDescriptorProto()
    fdp.ParseFromString(extract_serialized(src))
    apply_schema_edits(fdp)
    PB2.write_text(render(fdp))
    print(f"wrote {PB2} ({len(fdp.SerializeToString())} descriptor bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
