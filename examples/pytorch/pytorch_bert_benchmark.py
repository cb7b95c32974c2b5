"""BERT synthetic training under the torch frontend — the north-star
"BERT scripts run unchanged" shape (BASELINE.json): a HuggingFace
transformer wrapped in ``hvd.DistributedOptimizer`` with parameter
broadcast, synthetic token batches, sentences/sec reporting (the
protocol of ``pytorch_synthetic_benchmark.py``, applied to BERT).

  python examples/pytorch/pytorch_bert_benchmark.py --tiny
  python -m horovod_tpu.runner.launch -np 2 --cpu -- \
      python examples/pytorch/pytorch_bert_benchmark.py --tiny
"""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.dirname(_os.path.abspath(__file__)))))

import argparse
import time

import torch

import horovod_tpu.torch as hvd

parser = argparse.ArgumentParser()
parser.add_argument("--batch-size", type=int, default=8)
parser.add_argument("--seq-len", type=int, default=128)
parser.add_argument("--num-iters", type=int, default=10)
parser.add_argument("--warmup", type=int, default=2)
parser.add_argument("--tiny", action="store_true",
                    help="2-layer BERT config (CI-sized; torch runs "
                         "on host CPU — the collectives are the TPU "
                         "part)")
args = parser.parse_args()


def build_model():
    from transformers import BertConfig, BertForSequenceClassification

    if args.tiny:
        cfg = BertConfig(vocab_size=1024, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=256,
                         max_position_embeddings=args.seq_len,
                         num_labels=2)
    else:
        cfg = BertConfig(num_labels=2)    # bert-base shape
    return BertForSequenceClassification(cfg)


def main():
    hvd.init()
    torch.manual_seed(42)
    model = build_model()

    optimizer = torch.optim.AdamW(model.parameters(), lr=5e-5)
    optimizer = hvd.DistributedOptimizer(
        optimizer, named_parameters=model.named_parameters())
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(optimizer, root_rank=0)

    vocab = model.config.vocab_size
    gen = torch.Generator().manual_seed(hvd.rank())
    input_ids = torch.randint(0, vocab,
                              (args.batch_size, args.seq_len),
                              generator=gen)
    attention_mask = torch.ones_like(input_ids)
    labels = torch.randint(0, 2, (args.batch_size,), generator=gen)

    def step():
        optimizer.zero_grad()
        out = model(input_ids=input_ids,
                    attention_mask=attention_mask, labels=labels)
        out.loss.backward()
        optimizer.step()
        return float(out.loss.detach())

    for _ in range(args.warmup):
        loss = step()
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        loss = step()
    dt = time.perf_counter() - t0

    sps = args.batch_size * args.num_iters / dt
    if hvd.rank() == 0:
        print(f"loss {loss:.4f}")
        print(f"{sps:.1f} sentences/sec per rank, "
              f"{sps * hvd.size():.1f} total "
              f"({hvd.size()} ranks)")


if __name__ == "__main__":
    if _os.environ.get("HOROVOD_TPU_NUM_PROCS"):
        main()                          # horovodrun: one process per rank
    else:
        from horovod_tpu import run as hvd_run

        # transformers resolves its exports lazily and that machinery
        # is not thread-safe: resolve the names ONCE here, before the
        # rank threads race into build_model()
        from transformers import (  # noqa: F401
            BertConfig, BertForSequenceClassification,
        )
        hvd_run(main)                   # direct: rank threads
