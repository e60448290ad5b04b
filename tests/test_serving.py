"""Serving subsystem tests: InferenceModel, DynamicBatcher, HTTP server.

Reference analog: triton/qa/L0_parser and L0_e2e — parse a model, load a
strategy, serve requests end-to-end (SURVEY §2.9).
"""
import json
import threading
import urllib.request

import numpy as np
import pytest

from flexflow_tpu import CompMode, DataType, FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.serving import DynamicBatcher, InferenceModel, InferenceServer


@pytest.fixture(scope="module")
def served_model():
    cfg = FFConfig(batch_size=8)
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 16], name="x")
    t = ff.dense(x, 32, activation="relu")
    t = ff.dense(t, 4)
    out = ff.softmax(t)
    ff.compile(comp_mode=CompMode.INFERENCE, outputs=[out])
    return InferenceModel(ff, name="mlp", max_batch=8)


def test_inference_model_pads_and_slices(served_model):
    x = np.random.RandomState(0).randn(3, 16).astype(np.float32)
    (out,) = served_model.infer([x])
    assert out.shape == (3, 4)
    np.testing.assert_allclose(out.sum(axis=-1), 1.0, rtol=1e-5)
    # same rows regardless of batch padding
    (full,) = served_model.infer([np.concatenate([x, x[:1]], axis=0)])
    np.testing.assert_allclose(out, full[:3], rtol=1e-5)


def test_inference_model_validates(served_model):
    with pytest.raises(ValueError):
        served_model.infer([np.zeros((9, 16), np.float32)])  # > max_batch
    with pytest.raises(ValueError):
        served_model.infer([np.zeros((2, 7), np.float32)])  # bad shape


def test_metadata(served_model):
    md = served_model.metadata()
    assert md["name"] == "mlp"
    assert md["max_batch_size"] == 8
    assert md["inputs"][0]["shape"] == (16,)
    assert md["outputs"][0]["shape"] == (4,)


def test_dynamic_batcher_coalesces_and_scatters(served_model):
    b = DynamicBatcher(served_model, max_delay_s=0.02)
    b.start()
    try:
        xs = [np.random.RandomState(i).randn(2, 16).astype(np.float32) for i in range(4)]
        futures = [b.submit([x]) for x in xs]
        results = [f.result(timeout=30) for f in futures]
        for x, (out,) in zip(xs, results):
            (direct,) = served_model.infer([x])
            np.testing.assert_allclose(out, direct, rtol=1e-5)
    finally:
        b.stop()


def test_dynamic_batcher_concurrent_clients(served_model):
    b = DynamicBatcher(served_model, max_delay_s=0.01)
    b.start()
    errs = []

    def client(seed):
        try:
            x = np.random.RandomState(seed).randn(1, 16).astype(np.float32)
            (out,) = b.infer([x], timeout=30)
            (want,) = served_model.infer([x])
            np.testing.assert_allclose(out, want, rtol=1e-5)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    try:
        threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        b.stop()
    assert not errs, errs


def test_http_server_v2_protocol(served_model):
    server = InferenceServer(port=0)
    server.register(served_model)
    with server:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(f"{base}/v2/health/ready") as r:
            assert json.load(r)["ready"] is True
        with urllib.request.urlopen(f"{base}/v2/models/mlp") as r:
            md = json.load(r)
            assert md["max_batch_size"] == 8
        x = np.random.RandomState(3).randn(2, 16).astype(np.float32)
        req = json.dumps({
            "inputs": [{"name": "x", "shape": [2, 16], "datatype": "FP32",
                        "data": x.reshape(-1).tolist()}]
        }).encode()
        r = urllib.request.urlopen(
            urllib.request.Request(f"{base}/v2/models/mlp/infer", data=req,
                                   headers={"Content-Type": "application/json"}))
        resp = json.load(r)
        out = np.asarray(resp["outputs"][0]["data"]).reshape(resp["outputs"][0]["shape"])
        (want,) = served_model.infer([x])
        np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-6)


def test_http_server_errors(served_model):
    server = InferenceServer(port=0)
    server.register(served_model)
    with server:
        base = f"http://127.0.0.1:{server.port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/v2/models/nope")
        assert ei.value.code == 404
        bad = json.dumps({"inputs": []}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/v2/models/mlp/infer", data=bad))
        assert ei.value.code == 400


def test_from_onnx_with_strategy(tmp_path):
    """ONNX load + strategy file load (triton/src/onnx_parser.cc +
    strategy.cc analog)."""
    from tests.test_onnx_frontend import (Attr, GraphProto, Init, ModelProto,
                                          NodeProto, ValueInfo)

    w = Init("w", np.random.RandomState(0).randn(16, 4).astype(np.float32))
    g = GraphProto(
        node=[
            NodeProto("MatMul", ["x", "w"], ["h"], "mm"),
            NodeProto("Relu", ["h"], ["y"], "relu"),
        ],
        input=[ValueInfo("x")],
        output=[ValueInfo("y")],
        initializer=[w],
    )
    # export a data-parallel strategy for this graph, then serve with it
    from flexflow_tpu.parallel.strategy import data_parallel_strategy

    m = InferenceModel.from_onnx(ModelProto(g), {"x": [16]}, name="onnx_mlp", max_batch=4)
    strat = data_parallel_strategy(m.model.graph, num_devices=1)
    sf = tmp_path / "strategy.json"
    sf.write_text(strat.to_json())
    m2 = InferenceModel.from_onnx(
        ModelProto(g), {"x": [16]}, name="onnx_mlp2", max_batch=4, strategy_file=str(sf))
    x = np.random.RandomState(1).randn(2, 16).astype(np.float32)
    (a,) = m.infer([x])
    (b,) = m2.infer([x])
    assert a.shape == (2, 4)
    assert b.shape == (2, 4)


def test_from_onnx_serves_graph_weights():
    """ONNX initializer weights must reach the executor — outputs match
    the numpy computation, not random init."""
    from tests.test_onnx_frontend import (GraphProto, Init, ModelProto,
                                          NodeProto, ValueInfo)

    rs = np.random.RandomState(7)
    w = rs.randn(16, 4).astype(np.float32)
    g = GraphProto(
        node=[
            NodeProto("MatMul", ["x", "w"], ["h"], "mm"),
            NodeProto("Relu", ["h"], ["y"], "relu"),
        ],
        input=[ValueInfo("x")],
        output=[ValueInfo("y")],
        initializer=[Init("w", w)],
    )
    m = InferenceModel.from_onnx(ModelProto(g), {"x": [16]}, name="wcheck", max_batch=4)
    x = rs.randn(3, 16).astype(np.float32)
    (got,) = m.infer([x])
    np.testing.assert_allclose(got, np.maximum(x @ w, 0.0), rtol=1e-5, atol=1e-6)


def test_batcher_rejects_bad_shape_without_poisoning_batch(served_model):
    b = DynamicBatcher(served_model, max_delay_s=0.02)
    b.start()
    try:
        good = b.submit([np.zeros((1, 16), np.float32)])
        with pytest.raises(ValueError):
            b.submit([np.zeros((1, 5), np.float32)])  # rejected at submit
        (out,) = good.result(timeout=30)
        assert out.shape == (1, 4)
    finally:
        b.stop()


def test_batcher_restart_after_stop(served_model):
    b = DynamicBatcher(served_model, max_delay_s=0.01)
    b.start()
    b.infer([np.zeros((1, 16), np.float32)], timeout=30)
    b.stop()
    b.start()  # regression: stale None sentinel used to kill the collector
    (out,) = b.infer([np.zeros((1, 16), np.float32)], timeout=30)
    assert out.shape == (1, 4)
    b.stop()


# ---------------------------------------------------------------------------
# round-2 (VERDICT item 10 + ADVICE r1): strategy-parallel inference,
# model-repository lifecycle, batcher holdover, 400/500 separation
# ---------------------------------------------------------------------------


def test_strategy_parallel_inference_on_mesh():
    """A searched/tensor-parallel strategy drives multi-device inference
    (reference: triton/src/strategy.cc loading a partition strategy)."""
    from flexflow_tpu.models import TransformerConfig, build_transformer
    from flexflow_tpu.parallel.strategy import megatron_strategy

    cfg = TransformerConfig(num_layers=2, hidden_size=32, num_heads=2, ff_size=64, seq_length=8)
    config = FFConfig(batch_size=8, workers_per_node=8)
    m = build_transformer(config, cfg)
    strategy = megatron_strategy(m.graph, dp=4, tp=2)
    m.compile(comp_mode=CompMode.INFERENCE, strategy=strategy)
    assert dict(zip(m.mesh.axis_names, m.mesh.devices.shape)) == {"data": 4, "model": 2}
    im = InferenceModel(m, name="bert_tp", max_batch=8)
    x = np.random.RandomState(0).randn(3, 8, 32).astype(np.float32)
    (out,) = im.infer([x])
    assert out.shape == (3, 8, 32)
    assert np.all(np.isfinite(out))
    # per-device shards actually exist (tp weights split over "model")
    ex = m.executor
    sharded = [
        arr
        for ws in ex.params.values()
        for arr in ws.values()
        if arr.sharding.spec and "model" in str(arr.sharding.spec)
    ]
    assert sharded, "no tensor-parallel weight shards found"


def test_model_repository_roundtrip(tmp_path):
    from flexflow_tpu.serving import ModelRepository, save_model

    cfg = FFConfig(batch_size=4, workers_per_node=1)
    ff = FFModel(cfg)
    x = ff.create_tensor([4, 6], name="x")
    t = ff.dense(x, 8, activation="relu", name="fc1")
    out = ff.softmax(ff.dense(t, 3, name="fc2"))
    ff.compile(comp_mode=CompMode.INFERENCE, outputs=[out])
    im = InferenceModel(ff, name="repo_mlp", max_batch=4)
    xv = np.random.RandomState(1).randn(2, 6).astype(np.float32)
    (want,) = im.infer([xv])

    repo = ModelRepository(str(tmp_path))
    repo.save(im)
    assert repo.available() == ["repo_mlp"]
    im2 = repo.load("repo_mlp")
    (got,) = im2.infer([xv])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_repository_http_lifecycle(tmp_path):
    from flexflow_tpu.serving import ModelRepository, save_model

    cfg = FFConfig(batch_size=4, workers_per_node=1)
    ff = FFModel(cfg)
    x = ff.create_tensor([4, 6], name="x")
    out = ff.softmax(ff.dense(x, 3, name="fc"))
    ff.compile(comp_mode=CompMode.INFERENCE, outputs=[out])
    im = InferenceModel(ff, name="lc", max_batch=4)
    repo = ModelRepository(str(tmp_path))
    repo.save(im)

    def post(base, path):
        return urllib.request.urlopen(
            urllib.request.Request(base + path, data=b"{}", method="POST"))

    server = InferenceServer(port=0, repository=repo)
    with server:
        base = f"http://127.0.0.1:{server.port}"
        idx = json.load(post(base, "/v2/repository/index"))
        assert idx == [{"name": "lc", "state": "UNAVAILABLE"}]
        assert json.load(post(base, "/v2/repository/models/lc/load"))["state"] == "READY"
        idx = json.load(post(base, "/v2/repository/index"))
        assert idx[0]["state"] == "READY"
        # it serves
        xv = np.random.RandomState(2).randn(1, 6).astype(np.float32)
        req = json.dumps({"inputs": [{"name": "x", "shape": [1, 6], "datatype": "FP32",
                                      "data": xv.reshape(-1).tolist()}]}).encode()
        r = urllib.request.urlopen(urllib.request.Request(
            f"{base}/v2/models/lc/infer", data=req))
        assert r.status == 200
        # unload -> infer 404s
        assert json.load(post(base, "/v2/repository/models/lc/unload"))["state"] == "UNAVAILABLE"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/v2/models/lc/infer", data=req))
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            post(base, "/v2/repository/models/ghost/load")
        assert ei.value.code == 404


def test_batcher_holds_over_nonfitting_request(served_model):
    """ADVICE r1: a request that doesn't fit the current batch must lead
    the NEXT batch, not re-queue behind newer arrivals."""
    b = DynamicBatcher(served_model, max_delay_s=0.05)
    rs = np.random.RandomState(4)
    b.start()
    try:
        futs = [
            b.submit([rs.randn(5, 16).astype(np.float32)]),  # batch 1 (5/8)
            b.submit([rs.randn(6, 16).astype(np.float32)]),  # doesn't fit -> holds over
            b.submit([rs.randn(1, 16).astype(np.float32)]),  # joins batch 1
        ]
        outs = [f.result(timeout=30) for f in futs]
        assert [o[0].shape[0] for o in outs] == [5, 6, 1]
        assert b._pending is None
    finally:
        b.stop()


def test_server_returns_500_for_stopped_batcher(served_model):
    server = InferenceServer(port=0)
    server.register(served_model)
    with server:
        base = f"http://127.0.0.1:{server.port}"
        server.batchers["mlp"].stop()  # simulate backend failure
        x = np.zeros((1, 16), np.float32)
        req = json.dumps({"inputs": [{"name": "x", "shape": [1, 16], "datatype": "FP32",
                                      "data": x.reshape(-1).tolist()}]}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/v2/models/mlp/infer", data=req))
        assert ei.value.code == 500


# ------------------------------------------------------------------- gRPC
@pytest.fixture(scope="module")
def second_model():
    cfg = FFConfig(batch_size=8)
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 8], name="x")
    t = ff.dense(x, 16, activation="relu")
    out = ff.dense(t, 2)
    ff.compile(comp_mode=CompMode.INFERENCE, outputs=[out])
    return InferenceModel(ff, name="tiny", max_batch=8)


def _grpc_stub(port):
    import grpc

    from flexflow_tpu.serving import kserve_v2_pb2 as pb

    channel = grpc.insecure_channel(f"127.0.0.1:{port}")

    def call(method, req, resp_cls):
        fn = channel.unary_unary(
            f"/inference.GRPCInferenceService/{method}",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=resp_cls.FromString,
        )
        return fn(req, timeout=60)

    return channel, call, pb


def test_grpc_server_infer_and_metadata(served_model):
    """KServe v2 gRPC transport (VERDICT r2 next-round #9): metadata +
    infer round-trip matches a direct model call."""
    pytest.importorskip("grpc")
    from flexflow_tpu.serving.grpc_server import GrpcInferenceServer

    srv = GrpcInferenceServer(port=0)
    srv.register(served_model)
    with srv:
        channel, call, pb = _grpc_stub(srv.port)
        assert call("ServerReady", pb.ServerReadyRequest(), pb.ServerReadyResponse).ready
        assert call(
            "ModelReady", pb.ModelReadyRequest(name="mlp"), pb.ModelReadyResponse
        ).ready
        md = call(
            "ModelMetadata", pb.ModelMetadataRequest(name="mlp"), pb.ModelMetadataResponse
        )
        assert md.name == "mlp" and list(md.inputs[0].shape) == [16]

        x = np.random.RandomState(0).randn(2, 16).astype(np.float32)
        req = pb.ModelInferRequest(model_name="mlp")
        t = req.inputs.add()
        t.name = served_model.inputs[0].name
        t.datatype = "FP32"
        t.shape.extend(x.shape)
        t.contents.fp32_contents.extend(x.reshape(-1).tolist())
        resp = call("ModelInfer", req, pb.ModelInferResponse)
        out = np.asarray(resp.outputs[0].contents.fp32_contents, np.float32).reshape(
            list(resp.outputs[0].shape)
        )
        (direct,) = served_model.infer([x])
        np.testing.assert_allclose(out, np.asarray(direct), rtol=1e-5, atol=1e-6)
        channel.close()


def test_grpc_concurrent_clients_two_models(served_model, second_model):
    """Two models served concurrently, parallel clients on each — the
    multi-instance concurrency story of the reference's Triton backend
    (triton/src/instance.cc), shared-batcher edition."""
    pytest.importorskip("grpc")
    from flexflow_tpu.serving.grpc_server import GrpcInferenceServer

    srv = GrpcInferenceServer(port=0, max_workers=16)
    srv.register(served_model)
    srv.register(second_model)
    errors = []
    with srv:
        channel, call, pb = _grpc_stub(srv.port)

        def hit(model, n_feat, reps):
            try:
                rs = np.random.RandomState(hash(threading.current_thread().name) % 2**31)
                for _ in range(reps):
                    x = rs.randn(2, n_feat).astype(np.float32)
                    req = pb.ModelInferRequest(model_name=model.name)
                    t = req.inputs.add()
                    t.name = model.inputs[0].name
                    t.datatype = "FP32"
                    t.shape.extend(x.shape)
                    t.contents.fp32_contents.extend(x.reshape(-1).tolist())
                    resp = call("ModelInfer", req, pb.ModelInferResponse)
                    out = np.asarray(
                        resp.outputs[0].contents.fp32_contents, np.float32
                    ).reshape(list(resp.outputs[0].shape))
                    (want,) = model.infer([x])
                    np.testing.assert_allclose(out, np.asarray(want), rtol=1e-4, atol=1e-5)
            except Exception as e:  # surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=hit, args=(served_model, 16, 5)) for _ in range(4)
        ] + [
            threading.Thread(target=hit, args=(second_model, 8, 5)) for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        channel.close()
    assert not errors, errors[:2]


def test_grpc_shares_http_batchers(served_model):
    """Both transports drain ONE batching queue per model."""
    pytest.importorskip("grpc")
    from flexflow_tpu.serving.grpc_server import GrpcInferenceServer

    http = InferenceServer(port=0)
    http.register(served_model)
    grpc_srv = GrpcInferenceServer(port=0, http_server=http)
    assert grpc_srv.batchers is http.batchers
    http.start()
    try:
        with grpc_srv:
            channel, call, pb = _grpc_stub(grpc_srv.port)
            x = np.random.RandomState(1).randn(1, 16).astype(np.float32)
            req = pb.ModelInferRequest(model_name="mlp")
            t = req.inputs.add()
            t.name = served_model.inputs[0].name
            t.datatype = "FP32"
            t.shape.extend(x.shape)
            t.contents.fp32_contents.extend(x.reshape(-1).tolist())
            resp = call("ModelInfer", req, pb.ModelInferResponse)
            assert list(resp.outputs[0].shape) == [1, 4]
            # HTTP path still live on the same batcher
            body = json.dumps({
                "inputs": [{
                    "name": served_model.inputs[0].name,
                    "shape": [1, 16],
                    "datatype": "FP32",
                    "data": x.reshape(-1).tolist(),
                }]
            }).encode()
            r = urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{http.port}/v2/models/mlp/infer",
                    data=body,
                    headers={"Content-Type": "application/json"},
                ),
                timeout=30,
            )
            assert json.loads(r.read())["outputs"][0]["shape"] == [1, 4]
            channel.close()
    finally:
        http.stop()


def test_grpc_raw_contents_round_trip(served_model):
    """KServe v2 raw representation (VERDICT r4 ask #8): multi-sample
    requests with raw_input_contents bytes round-trip through the server
    and come back as raw_output_contents matching a direct model call —
    the Triton-client fast path that sidesteps repeated-float packing."""
    pytest.importorskip("grpc")
    from flexflow_tpu.serving.grpc_server import GrpcInferenceServer

    srv = GrpcInferenceServer(port=0)
    srv.register(served_model)
    with srv:
        channel, call, pb = _grpc_stub(srv.port)
        x = np.random.RandomState(1).randn(4, 16).astype(np.float32)
        req = pb.ModelInferRequest(model_name="mlp")
        t = req.inputs.add()
        t.name = served_model.inputs[0].name
        t.datatype = "FP32"
        t.shape.extend(x.shape)
        req.raw_input_contents.append(x.tobytes())
        resp = call("ModelInfer", req, pb.ModelInferResponse)
        assert resp.raw_output_contents, "raw request must get a raw response"
        assert not resp.outputs[0].contents.fp32_contents
        out = np.frombuffer(resp.raw_output_contents[0], np.float32).reshape(
            list(resp.outputs[0].shape)
        )
        (direct,) = served_model.infer([x])
        np.testing.assert_allclose(out, np.asarray(direct), rtol=1e-5, atol=1e-6)

        # malformed: raw count must match inputs count
        bad = pb.ModelInferRequest(model_name="mlp")
        tb = bad.inputs.add()
        tb.name = served_model.inputs[0].name
        tb.datatype = "FP32"
        tb.shape.extend(x.shape)
        bad.raw_input_contents.append(x.tobytes())
        bad.raw_input_contents.append(x.tobytes())
        import grpc as _grpc

        with pytest.raises(_grpc.RpcError) as ei:
            call("ModelInfer", bad, pb.ModelInferResponse)
        assert ei.value.code() == _grpc.StatusCode.INVALID_ARGUMENT
        channel.close()


def test_the_listener_holds_a_closed_loops_burst_of_connections():
    """96 clients connect in the same instant (a closed loop's start)
    while the accept loop is held up for 50 ms (the interpreter's lock,
    under handler threads parsing long prompts): every one is served.
    With ``socketserver``'s backlog of 5 most of them read
    ``ConnectionResetError`` once they send their request, which a
    benchmark run counts as failed requests (PR 35)."""
    import http.client
    import time
    from http.server import BaseHTTPRequestHandler

    from flexflow_tpu.serving.server import _Listener

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            self.send_response(200)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"ok")

        def log_message(self, *args):
            pass

    listener = _Listener(("127.0.0.1", 0), Handler)
    outcomes = []

    def client():
        try:
            conn = http.client.HTTPConnection("127.0.0.1", listener.server_address[1], timeout=60)
            conn.request("POST", "/v2/models/lm/generate", body=b"7" * 20000)
            outcomes.append(conn.getresponse().read())
        except OSError as e:
            outcomes.append(e)

    clients = [threading.Thread(target=client) for _ in range(96)]
    for c in clients:
        c.start()
    time.sleep(0.05)
    serving = threading.Thread(target=listener.serve_forever, daemon=True)
    serving.start()
    for c in clients:
        c.join()
    listener.shutdown()
    listener.server_close()
    assert outcomes == [b"ok"] * 96
