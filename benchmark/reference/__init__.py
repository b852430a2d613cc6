"""Plain references: each configuration's TRAIN-phase forward in straight
``jax.numpy``/``lax`` at float32, written from the published description and
importing nothing of the program.  ``logits(params, x)`` takes the program's
parameter tree (``{layer name: [blobs]}``, Caffe's blob layouts) and the
cropped, mean-subtracted float batch; loss and gradients follow from it in
``plain_ops.step``."""
