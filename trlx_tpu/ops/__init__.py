from trlx_tpu.ops.generation import LENGTH_BUCKETS, generate, left_pad_batch, pad_to_bucket
from trlx_tpu.ops.sampling import sample_token
