"""ImageNet ResNet-50 training with the torch frontend (reference
``examples/pytorch/pytorch_imagenet_resnet50.py``: same workflow —
DistributedSampler-style sharding, DistributedOptimizer with
batches-per-allreduce accumulation, lr warmup scaled by world size,
rank-0 checkpointing, averaged metrics).

Real data needs torchvision (gated; absent from this image):
    python -m horovod_tpu.runner.launch -np 4 --cpu -- \
        python examples/pytorch/pytorch_imagenet_resnet50.py \
        --train-dir /data/train --val-dir /data/val
Synthetic smoke mode runs anywhere:
    python examples/pytorch/pytorch_imagenet_resnet50.py --synthetic
"""

import os as _os
import sys as _sys

# allow running straight from a source checkout
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.dirname(_os.path.abspath(__file__)))))


import argparse
import os

import numpy as np
import torch
import torch.nn.functional as F
import torch.utils.data.distributed

import horovod_tpu.torch as hvd

parser = argparse.ArgumentParser()
parser.add_argument("--train-dir", default=None)
parser.add_argument("--val-dir", default=None)
parser.add_argument("--synthetic", action="store_true",
                    help="random data + a compact conv net (no "
                         "torchvision needed)")
parser.add_argument("--batch-size", type=int, default=32)
parser.add_argument("--epochs", type=int, default=1)
parser.add_argument("--batches-per-allreduce", type=int, default=1,
                    help="accumulate this many micro-batches locally "
                         "before each allreduce")
parser.add_argument("--base-lr", type=float, default=0.0125)
parser.add_argument("--warmup-epochs", type=float, default=5)
parser.add_argument("--fp16-allreduce", action="store_true")
parser.add_argument("--use-adasum", action="store_true")
parser.add_argument("--checkpoint-format",
                    default="checkpoint-{epoch}.pt")
parser.add_argument("--steps-per-epoch", type=int, default=8,
                    help="synthetic mode only")
args = parser.parse_args()

hvd.init()
torch.manual_seed(42 + hvd.rank())


def make_model_and_data():
    if args.synthetic:
        class TinyResNet(torch.nn.Module):
            def __init__(self, classes=100):
                super().__init__()
                self.stem = torch.nn.Conv2d(3, 32, 3, 2, 1)
                self.b1 = torch.nn.Conv2d(32, 64, 3, 2, 1)
                self.b2 = torch.nn.Conv2d(64, 128, 3, 2, 1)
                self.head = torch.nn.Linear(128, classes)

            def forward(self, x):
                x = F.relu(self.stem(x))
                x = F.relu(self.b1(x))
                x = F.relu(self.b2(x))
                x = x.mean(dim=(2, 3))
                return self.head(x)

        model = TinyResNet()
        data = [(torch.randn(args.batch_size, 3, 64, 64),
                 torch.randint(0, 100, (args.batch_size,)))
                for _ in range(args.steps_per_epoch)]
        return model, data, None
    try:
        import torchvision
        from torchvision import datasets, models, transforms
    except ImportError as exc:
        raise SystemExit(
            "torchvision is required for real ImageNet training "
            "(pip install torchvision), or pass --synthetic") from exc
    model = models.resnet50()
    tf_train = transforms.Compose([
        transforms.RandomResizedCrop(224),
        transforms.RandomHorizontalFlip(),
        transforms.ToTensor(),
        transforms.Normalize((0.485, 0.456, 0.406),
                             (0.229, 0.224, 0.225)),
    ])
    train_ds = datasets.ImageFolder(args.train_dir, tf_train)
    # shard the dataset across ranks (the reference uses
    # torch.utils.data.distributed.DistributedSampler the same way)
    sampler = torch.utils.data.distributed.DistributedSampler(
        train_ds, num_replicas=hvd.size(), rank=hvd.rank())
    loader = torch.utils.data.DataLoader(
        train_ds, batch_size=args.batch_size, sampler=sampler)
    val_loader = None
    if args.val_dir:
        tf_val = transforms.Compose([
            transforms.Resize(256), transforms.CenterCrop(224),
            transforms.ToTensor(),
            transforms.Normalize((0.485, 0.456, 0.406),
                                 (0.229, 0.224, 0.225)),
        ])
        val_ds = datasets.ImageFolder(args.val_dir, tf_val)
        val_sampler = torch.utils.data.distributed.DistributedSampler(
            val_ds, num_replicas=hvd.size(), rank=hvd.rank())
        val_loader = torch.utils.data.DataLoader(
            val_ds, batch_size=args.batch_size, sampler=val_sampler)
    return model, loader, val_loader


model, train_loader, val_loader = make_model_and_data()

# scale lr by total batch parallelism; Adasum converges with the base lr
lr_scaler = 1 if args.use_adasum else \
    hvd.size() * args.batches_per_allreduce
optimizer = torch.optim.SGD(model.parameters(),
                            lr=args.base_lr * lr_scaler,
                            momentum=0.9, weight_decay=5e-5)
compression = hvd.Compression.fp16 if args.fp16_allreduce else \
    hvd.Compression.none
optimizer = hvd.DistributedOptimizer(
    optimizer, named_parameters=model.named_parameters(),
    compression=compression,
    backward_passes_per_step=args.batches_per_allreduce,
    op=hvd.Adasum if args.use_adasum else hvd.Average)

hvd.broadcast_parameters(model.state_dict(), root_rank=0)
hvd.broadcast_optimizer_state(optimizer, root_rank=0)


def save_checkpoint(epoch):
    if hvd.rank() == 0:
        torch.save({"model": model.state_dict(),
                    "optimizer": optimizer.state_dict()},
                   args.checkpoint_format.format(epoch=epoch))


STEPS_PER_EPOCH = args.steps_per_epoch if args.synthetic else \
    max(len(train_loader), 1)


def adjust_learning_rate(epoch, step):
    """Gradual lr warmup from base_lr to base_lr*scaler over
    --warmup-epochs (reference example's adjust_learning_rate /
    'ImageNet in 1 Hour' recipe), constant afterwards."""
    progress = epoch + step / STEPS_PER_EPOCH
    if progress < args.warmup_epochs:
        factor = (1.0 + (lr_scaler - 1.0) *
                  progress / args.warmup_epochs) / lr_scaler
    else:
        factor = 1.0
    for group in optimizer.param_groups:
        group["lr"] = args.base_lr * lr_scaler * factor


for epoch in range(args.epochs):
    model.train()
    sampler = getattr(train_loader, "sampler", None)
    if hasattr(sampler, "set_epoch"):
        # reshuffle differently each epoch (reference example does the
        # same; without it every epoch repeats one shuffled order)
        sampler.set_epoch(epoch)
    seen, loss_sum, pending = 0, 0.0, False
    for step, (data, target) in enumerate(train_loader):
        adjust_learning_rate(epoch, step)
        if step % args.batches_per_allreduce == 0:
            optimizer.zero_grad()
        loss = F.cross_entropy(model(data), target)
        # accumulated micro-batches are summed by autograd: divide so
        # the aggregate matches one full-batch gradient (the lr scaler
        # already accounts for the larger effective batch)
        (loss / args.batches_per_allreduce).backward()
        pending = True
        if (step + 1) % args.batches_per_allreduce == 0:
            optimizer.step()
            pending = False
        loss_sum += loss.item() * data.size(0)
        seen += data.size(0)
    if pending:
        # trailing micro-batches: synchronize() flushes the partial
        # accumulation so those samples still train
        optimizer.step()
    # averaged epoch metric across ranks (MetricAverageCallback role)
    avg = hvd.allreduce(np.array([loss_sum / max(seen, 1)],
                                 np.float32), op=hvd.Average,
                        name=f"epoch_loss.{epoch}")
    if hvd.rank() == 0:
        print(f"epoch {epoch}: mean loss {float(avg[0]):.4f} "
              f"(size {hvd.size()})")
    if val_loader is not None:
        model.eval()
        correct, count = 0, 0
        with torch.no_grad():
            for data, target in val_loader:
                pred = model(data).argmax(dim=1)
                correct += int((pred == target).sum())
                count += target.size(0)
        acc = hvd.allreduce(np.array([correct / max(count, 1)],
                                     np.float32), op=hvd.Average,
                            name=f"val_acc.{epoch}")
        if hvd.rank() == 0:
            print(f"epoch {epoch}: val accuracy {float(acc[0]):.4f}")
    save_checkpoint(epoch)

if args.checkpoint_format.startswith("checkpoint-") and \
        hvd.rank() == 0 and args.synthetic:
    # don't litter the checkout in smoke mode
    for epoch in range(args.epochs):
        path = args.checkpoint_format.format(epoch=epoch)
        if os.path.exists(path):
            os.remove(path)
print(f"done rank {hvd.rank()}")
