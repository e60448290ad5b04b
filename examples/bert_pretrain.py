"""BERT-style encoder training (reference: examples/cpp/Transformer,
scripts/osdi22ae/bert.sh: searched strategy vs --only-data-parallel).

  python examples/bert_pretrain.py -b 8 --budget 30
  python examples/bert_pretrain.py -b 8 --only-data-parallel
"""
import sys

sys.path.insert(0, ".")
import numpy as np

from examples.common import Timer

from flexflow_tpu import DataType, FFConfig, LossType, SGDOptimizer
from flexflow_tpu.models import TransformerConfig, build_transformer


def main():
    from flexflow_tpu.device import enable_compile_cache

    enable_compile_cache()
    config = FFConfig.from_args()
    cfg = TransformerConfig(
        num_layers=4, hidden_size=512, num_heads=8, ff_size=2048, seq_length=128,
    )
    model = build_transformer(config, cfg)
    model.compile(optimizer=SGDOptimizer(lr=config.learning_rate), loss_type=LossType.MEAN_SQUARED_ERROR)
    if model._search_result is not None:
        r = model._search_result
        print(f"search: cost {r.best_cost*1e3:.3f} ms/iter, mesh {model.strategy.axis_sizes}")
    rs = np.random.RandomState(0)
    n = 2 * config.batch_size
    x = rs.randn(n, cfg.seq_length, cfg.hidden_size).astype(np.float32)
    y = rs.randn(n, cfg.seq_length, cfg.hidden_size).astype(np.float32)
    with Timer() as t:
        model.fit([x], y, epochs=config.epochs)
    print(f"done in {t.seconds:.2f}s")


if __name__ == "__main__":
    main()
