"""Serve a compiled model over HTTP (reference: the triton/ backend —
here the server is in-framework, speaking the Triton v2 protocol).

  python examples/serving_demo.py --port 8000
  curl localhost:8000/v2/health/ready
  curl localhost:8000/v2/models/mlp
"""
import sys

sys.path.insert(0, ".")
import argparse

from flexflow_tpu import CompMode, FFConfig, FFModel
from flexflow_tpu.serving import InferenceModel, InferenceServer


def main():
    from flexflow_tpu.device import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--max-batch", type=int, default=32)
    args, _ = ap.parse_known_args()

    ff = FFModel(FFConfig(batch_size=args.max_batch))
    x = ff.create_tensor([args.max_batch, 64], name="x")
    t = ff.dense(x, 256, activation="relu")
    t = ff.dense(t, 10)
    out = ff.softmax(t)
    ff.compile(comp_mode=CompMode.INFERENCE, outputs=[out])

    server = InferenceServer(port=args.port)
    server.register(InferenceModel(ff, name="mlp", max_batch=args.max_batch))
    server.start()
    print(f"serving on http://127.0.0.1:{server.port} — POST /v2/models/mlp/infer")
    try:
        import time

        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
