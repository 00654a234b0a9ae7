"""Model zoo: reference net architectures as netconfig strings.

These mirror the reference's example configs (the de-facto model zoo of
cxxnet): AlexNet (example/ImageNet/ImageNet.conf:26-130), the MNIST MLP/conv
recipes, and the kaggle_bowl plankton net. Input sizes are parameterizable so
tiny variants compile fast in tests and multi-chip dry runs.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from .nnet.trainer import Trainer
from .utils.config import parse_config_string


ALEXNET_NETCONFIG = """
netconfig=start
layer[0->1] = conv:conv1
  kernel_size = 11
  stride = 4
  nchannel = 96
layer[1->2] = relu
layer[2->3] = max_pooling
  kernel_size = 3
  stride = 2
layer[3->4] = lrn
  local_size = 5
  alpha = 0.001
  beta = 0.75
  knorm = 1
layer[4->5] = conv:conv2
  ngroup = 2
  nchannel = 256
  kernel_size = 5
  pad = 2
layer[5->6] = relu
layer[6->7] = max_pooling
  kernel_size = 3
  stride = 2
layer[7->8] = lrn
  local_size = 5
  alpha = 0.001
  beta = 0.75
  knorm = 1
layer[8->9] = conv:conv3
  nchannel = 384
  kernel_size = 3
  pad = 1
layer[9->10]= relu
layer[10->11] = conv:conv4
  nchannel = 384
  ngroup = 2
  kernel_size = 3
  pad = 1
layer[11->12] = relu
layer[12->13] = conv:conv5
  nchannel = 256
  ngroup = 2
  kernel_size = 3
  pad = 1
  init_bias = 1.0
layer[13->14] = relu
layer[14->15] = max_pooling
  kernel_size = 3
  stride = 2
layer[15->16] = flatten
layer[16->17] = fullc:fc6
  nhidden = 4096
  init_sigma = 0.005
  init_bias = 1.0
layer[17->18] = relu
layer[18->18] = dropout
  threshold = 0.5
layer[18->19] = fullc:fc7
  nhidden = 4096
  init_sigma = 0.005
  init_bias = 1.0
layer[19->20] = relu
layer[20->20] = dropout
  threshold = 0.5
layer[20->21] = fullc:fc8
  nhidden = 1000
layer[21->21] = softmax
netconfig=end
"""

ALEXNET_GLOBALS = """
momentum = 0.9
wmat:lr  = 0.01
wmat:wd  = 0.0005
bias:wd  = 0.000
bias:lr  = 0.02
lr:schedule = expdecay
lr:gamma = 0.1
lr:step = 100000
random_type = xavier
metric = error
"""


def alexnet_trainer(batch_size: int = 256, input_hw: int = 227,
                    dev: str = "tpu", extra_cfg: str = "") -> Trainer:
    """Build an AlexNet trainer with the reference recipe. input_hw can be
    shrunk (>= 67) for fast compile checks; 227 is the paper/reference size."""
    assert input_hw >= 67, "AlexNet needs input >= 67 with these strides"
    conf = (ALEXNET_NETCONFIG + ALEXNET_GLOBALS +
            "input_shape = 3,%d,%d\n" % (input_hw, input_hw) +
            "batch_size = %d\n" % batch_size +
            "dev = %s\n" % dev + extra_cfg)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _inception_block(idx: int, node_in: str, nch: int) -> Tuple[str, str]:
    """One inception-style module: split 1->3, parallel 1x1/3x3/5x5 conv
    towers, ch_concat 3->1 (reference DAG features:
    src/layer/split_layer-inl.hpp, ch_concat at layer_impl-inl.hpp:61-62).
    Returns (netconfig text, output node name)."""
    p = "i%d" % idx
    txt = f"""
layer[{node_in}->{p}a,{p}b,{p}c] = split
layer[{p}a->{p}t1] = conv:{p}_1x1
  kernel_size = 1
  nchannel = {nch}
layer[{p}t1->{p}r1] = relu
layer[{p}b->{p}t3] = conv:{p}_3x3
  kernel_size = 3
  pad = 1
  nchannel = {nch}
layer[{p}t3->{p}r3] = relu
layer[{p}c->{p}t5] = conv:{p}_5x5
  kernel_size = 5
  pad = 2
  nchannel = {nch}
layer[{p}t5->{p}r5] = relu
layer[{p}r1,{p}r3,{p}r5->{p}out] = ch_concat
"""
    return txt, p + "out"


def inception_small_netconfig(n_blocks: int = 2, nch: int = 16,
                              n_class: int = 10) -> str:
    """A small GoogLeNet-flavored net: stem conv, n inception modules,
    global pooling head. Exercises split / parallel towers / ch_concat."""
    txt = """
netconfig=start
layer[0->stem] = conv:stem
  kernel_size = 3
  stride = 1
  pad = 1
  nchannel = %d
layer[stem->stemr] = relu
""" % nch
    node = "stemr"
    for i in range(n_blocks):
        blk, node = _inception_block(i, node, nch)
        txt += blk
    txt += """
layer[%s->gp] = avg_pooling
  kernel_size = 4
  stride = 4
layer[gp->fl] = flatten
layer[fl->out] = fullc:head
  nhidden = %d
layer[+0] = softmax
netconfig=end
random_type = xavier
metric = error
""" % (node, n_class)
    return txt


def inception_trainer(batch_size: int = 16, input_hw: int = 16,
                      dev: str = "cpu", n_blocks: int = 2,
                      extra_cfg: str = "") -> Trainer:
    conf = (inception_small_netconfig(n_blocks=n_blocks) +
            "input_shape = 3,%d,%d\n" % (input_hw, input_hw) +
            "batch_size = %d\n" % batch_size +
            "updater = adam\neta = 0.003\n" +
            "dev = %s\n" % dev + extra_cfg)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _gnet_inception(name: str, node_in: str,
                    c1: int, c3r: int, c3: int, c5r: int, c5: int,
                    cp: int) -> Tuple[str, str]:
    """One GoogLeNet (Inception-v1) module: 1x1 / 1x1->3x3 / 1x1->5x5 /
    3x3-pool->1x1 towers, channel-concatenated (Szegedy et al. 2014).
    Expressed purely in the netconfig DSL (split + ch_concat)."""
    p = name
    txt = f"""
layer[{node_in}->{p}a,{p}b,{p}c,{p}d] = split
layer[{p}a->{p}t1] = conv:{p}_1x1
  kernel_size = 1
  nchannel = {c1}
layer[{p}t1->{p}o1] = relu
layer[{p}b->{p}t3r] = conv:{p}_3x3r
  kernel_size = 1
  nchannel = {c3r}
layer[{p}t3r->{p}r3r] = relu
layer[{p}r3r->{p}t3] = conv:{p}_3x3
  kernel_size = 3
  pad = 1
  nchannel = {c3}
layer[{p}t3->{p}o3] = relu
layer[{p}c->{p}t5r] = conv:{p}_5x5r
  kernel_size = 1
  nchannel = {c5r}
layer[{p}t5r->{p}r5r] = relu
layer[{p}r5r->{p}t5] = conv:{p}_5x5
  kernel_size = 5
  pad = 2
  nchannel = {c5}
layer[{p}t5->{p}o5] = relu
layer[{p}d->{p}pp] = max_pooling
  kernel_size = 3
  stride = 1
  pad = 1
layer[{p}pp->{p}tp] = conv:{p}_proj
  kernel_size = 1
  nchannel = {cp}
layer[{p}tp->{p}op] = relu
layer[{p}o1,{p}o3,{p}o5,{p}op->{p}out] = ch_concat
"""
    return txt, p + "out"


# (c1, c3r, c3, c5r, c5, pool_proj) per module — the paper's Table 1
GOOGLENET_MODULES = {
    "i3a": (64, 96, 128, 16, 32, 32),
    "i3b": (128, 128, 192, 32, 96, 64),
    "i4a": (192, 96, 208, 16, 48, 64),
    "i4b": (160, 112, 224, 24, 64, 64),
    "i4c": (128, 128, 256, 24, 64, 64),
    "i4d": (112, 144, 288, 32, 64, 64),
    "i4e": (256, 160, 320, 32, 128, 128),
    "i5a": (256, 160, 320, 32, 128, 128),
    "i5b": (384, 192, 384, 48, 128, 128),
}


def googlenet_netconfig(n_class: int = 1000, final_pool: int = 7) -> str:
    """GoogLeNet / Inception-v1 (the BASELINE.json 'ImageNet GoogLeNet'
    config): stem, 9 inception modules with maxpools between stages, global
    avg-pool head. LRN runs the Pallas kernel on TPU."""
    txt = """
netconfig=start
layer[0->n1] = conv:conv1
  kernel_size = 7
  stride = 2
  pad = 3
  nchannel = 64
layer[n1->n2] = relu
layer[n2->n3] = max_pooling
  kernel_size = 3
  stride = 2
layer[n3->n4] = lrn
  local_size = 5
  alpha = 0.0001
  beta = 0.75
  knorm = 1
layer[n4->n5] = conv:conv2r
  kernel_size = 1
  nchannel = 64
layer[n5->n6] = relu
layer[n6->n7] = conv:conv2
  kernel_size = 3
  pad = 1
  nchannel = 192
layer[n7->n8] = relu
layer[n8->n9] = lrn
  local_size = 5
  alpha = 0.0001
  beta = 0.75
  knorm = 1
layer[n9->n10] = max_pooling
  kernel_size = 3
  stride = 2
"""
    node = "n10"
    for mod in ("i3a", "i3b"):
        blk, node = _gnet_inception(mod, node, *GOOGLENET_MODULES[mod])
        txt += blk
    txt += """
layer[%s->p3] = max_pooling
  kernel_size = 3
  stride = 2
""" % node
    node = "p3"
    for mod in ("i4a", "i4b", "i4c", "i4d", "i4e"):
        blk, node = _gnet_inception(mod, node, *GOOGLENET_MODULES[mod])
        txt += blk
    txt += """
layer[%s->p4] = max_pooling
  kernel_size = 3
  stride = 2
""" % node
    node = "p4"
    for mod in ("i5a", "i5b"):
        blk, node = _gnet_inception(mod, node, *GOOGLENET_MODULES[mod])
        txt += blk
    txt += """
layer[%(node)s->gp] = avg_pooling
  kernel_size = %(fp)d
  stride = %(fp)d
layer[gp->fl] = flatten
layer[fl->fd] = dropout
  threshold = 0.4
layer[fd->out] = fullc:loss_fc
  nhidden = %(ncls)d
layer[+0] = softmax
netconfig=end
random_type = xavier
metric = error
""" % {"node": node, "fp": final_pool, "ncls": n_class}
    return txt


def googlenet_trainer(batch_size: int = 128, input_hw: int = 224,
                      dev: str = "tpu", n_class: int = 1000,
                      extra_cfg: str = "") -> Trainer:
    """GoogLeNet with the standard ImageNet recipe shape (224x224). For
    tests, input_hw can shrink (>= 32); the final avg-pool adapts."""
    assert input_hw >= 32
    final_pool = max(input_hw // 32, 1)
    conf = (googlenet_netconfig(n_class=n_class, final_pool=final_pool) +
            "input_shape = 3,%d,%d\n" % (input_hw, input_hw) +
            "batch_size = %d\n" % batch_size +
            "eta = 0.01\nmomentum = 0.9\nwd = 0.0002\n" +
            "dev = %s\n" % dev + extra_cfg)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _transformer_block(p: str, node_in: str, dim: int, nhead: int,
                       ffn: int, attn_keys: str = "",
                       norm=False, ffn_kind: str = "conv",
                       ffn_keys: str = "", norm_keys: str = "",
                       init_sigma: Optional[float] = 0.05,
                       router_on_input: bool = True,
                       mixer: str = "attention"
                       ) -> Tuple[str, str]:
    """One transformer block in the DSL, shared by the LM, ViT and
    mixture-of-experts builders so the block shape lives in one place.
    Residuals connect the BLOCK INPUT (pre-norm form): out = x +
    mix(norm(x)), then + ffn(norm(.)). ``norm``: False, ``"batch_norm"``
    (True; moving_average) or ``"rmsnorm"`` before each sub-block.
    ``mixer``: ``"attention"`` (layer ``<p>_att`` of ``nhead`` heads) or
    ``"shortconv"`` (layer ``<p>_conv``, a gated short causal conv);
    attn_keys are the mixer's extra config lines (causal/rope/GQA/window/
    head_dim, or the conv's kernel). ``ffn_kind``: ``"conv"`` (two 1x1
    convs of width ``ffn`` around a relu), ``"ffn"`` (one dense gated
    ``ffn`` layer ``<p>_ffn`` of width ``ffn``) or ``"moe"`` (one ``moe``
    layer of expert width ``ffn`` whose router reads the block's input,
    before the norm and the mixer, or with ``router_on_input`` False the
    normed node its experts read: the layer's one-input form);
    ``ffn_keys`` are the ffn's lines. ``norm_keys``: lines of both norm
    layers. ``init_sigma`` None leaves the weights' spread to the conf's
    global key."""
    def keys(text):
        return "".join("  %s\n" % ln.strip()
                       for ln in text.splitlines() if ln.strip())
    norm = "batch_norm" if norm is True else norm
    norm_keys = keys(("moving_average = 1\n" if norm == "batch_norm"
                      else "") + norm_keys)
    short = {"batch_norm": "bn", "rmsnorm": "rn", False: ""}[norm]
    txt = ""
    att_in = node_in
    if norm:
        txt += ("layer[%(in)s->%(p)sn1] = %(norm)s:%(p)s_%(s)s1\n%(nk)s"
                % {"in": node_in, "p": p, "norm": norm, "s": short,
                   "nk": norm_keys})
        att_in = p + "n1"
    sigma = "" if init_sigma is None else \
        "  init_sigma = %.10g\n" % init_sigma
    node, layer = {"attention": ("att", "attention:%s_att\n  nhead = %d"
                                 % (p, nhead)),
                   "shortconv": ("conv", "shortconv:%s_conv" % p)}[mixer]
    txt += """layer[%(ai)s->%(p)s%(n)s] = %(lay)s
%(sg)s%(ak)slayer[%(in)s,%(p)s%(n)s->%(p)sres1] = add
""" % {"ai": att_in, "in": node_in, "p": p, "n": node, "lay": layer,
       "sg": sigma, "ak": keys(attn_keys)}
    ffn_in = p + "res1"
    if norm:
        txt += ("layer[%(p)sres1->%(p)sn2] = %(norm)s:%(p)s_%(s)s2\n%(nk)s"
                % {"p": p, "norm": norm, "s": short, "nk": norm_keys})
        ffn_in = p + "n2"
    if ffn_kind in ("moe", "ffn"):
        txt += """layer[%(fi)s->%(p)sf2] = %(k)s:%(p)s_%(k)s
  nhidden = %(ffn)d
%(fk)s""" % {"fi": ffn_in + (("," + node_in) if router_on_input
                             and ffn_kind == "moe" else ""),
            "p": p, "k": ffn_kind, "ffn": ffn, "fk": keys(ffn_keys)}
    else:
        txt += """layer[%(fi)s->%(p)sf1] = conv:%(p)s_ffn1
  kernel_size = 1
  nchannel = %(ffn)d
%(sg)slayer[%(p)sf1->%(p)sr] = relu
layer[%(p)sr->%(p)sf2] = conv:%(p)s_ffn2
  kernel_size = 1
  nchannel = %(dim)d
%(sg)s""" % {"fi": ffn_in, "p": p, "ffn": ffn, "dim": dim, "sg": sigma}
    txt += "layer[%(p)sres1,%(p)sf2->%(p)sout] = add\n" % {"p": p}
    return txt, p + "out"


def _decoder_lm(vocab: int, dim: int, nhead: int, blocks, eps: float,
                norm_f_keys: str = "", loss_keys: str = "") -> str:
    """The decoder language models' netconfig: embed (normal(0, 1)) ->
    one ``_transformer_block`` (rmsnorm, the weights' spread the global
    normal(0, 0.02)) a dict of ``blocks``, whose entries are that
    function's keyword arguments (``ffn`` the FFN's width, ``mixer``,
    ``attn_keys``, ``ffn_kind``, ``ffn_keys``, ``router_on_input``, and
    ``nhead`` where a block has heads of its own count; ``prefix``, the
    block's names, ``b<i>`` unless given) -> rmsnorm ``norm_f`` -> untied
    vocab head, no bias -> per-position softmax. ``norm_f_keys`` /
    ``loss_keys``: further lines of the last norm and of the loss."""
    txt = """
netconfig = start
layer[0->emb] = embed:emb
  vocab_size = %d
  nhidden = %d
  init_sigma = 1
""" % (vocab, dim)
    node = "emb"
    for i, blk in enumerate(blocks):
        blk = dict(blk)
        t, node = _transformer_block(
            blk.pop("prefix", "b%d" % i), node, dim, blk.pop("nhead", nhead),
            norm="rmsnorm", norm_keys="eps = %.10g\n" % eps,
            init_sigma=None, **blk)
        txt += "\n" + t
    txt += """
layer[%s->nf] = rmsnorm:norm_f
  eps = %.10g
%slayer[nf->logits] = conv:head
  kernel_size = 1
  nchannel = %d
  no_bias = 1
layer[+0] = softmax
  seq = 1
%snetconfig = end
random_type = gaussian
init_sigma = 0.02
""" % (node, eps, norm_f_keys, vocab, loss_keys)
    return txt


def _lm_conf(netconfig: str, adamw: str, rows: int, batch_size: int,
             dev: str, extra_cfg: str, labels: str) -> str:
    """A decoder language model's whole training conf: the netconfig, its
    AdamW block, the shapes (``rows`` a sequence) and the label fields."""
    return (netconfig + adamw + "input_shape = 1,1,%d\n" % rows +
            "batch_size = %d\n" % batch_size + labels +
            "dev = %s\n" % dev + extra_cfg)


def _moe_keys(n_expert: int, top_k: int, n_held: int, expert_offset: int,
              act: str, remat: str, extra: str = "") -> str:
    """The lines of one chip's share of a ``moe`` layer."""
    return ("nexpert = %d\ntop_k = %d\nnexpert_held = %d\n"
            "expert_offset = %d\nexpert_act = %s\n%sremat = %d\n"
            % (n_expert, top_k, n_held or n_expert, expert_offset, act,
               extra, "moe" in remat.split()))


def transformer_lm_netconfig(vocab: int, dim: int = 64, nhead: int = 4,
                             nlayer: int = 2, ffn_mult: int = 2,
                             attn_extra: str = "") -> str:
    """Decoder-only transformer LM from the netconfig DSL (beyond the
    reference — the long-context model family): embed -> n x [causal
    attention + residual, 1x1-conv FFN + residual] -> vocab head ->
    per-position softmax (seq = 1). Residuals use the `add` layer.
    ``attn_extra``: extra per-attention-layer keys (e.g. "nkvhead = 2\\n
    attn_window = 1024\\nrope = 1\\n" for a GQA sliding-window recipe)."""
    txt = """
netconfig = start
layer[+1:emb] = embed:emb
  vocab_size = %d
  nhidden = %d
  pos_embed = 1
  init_sigma = 0.05
""" % (vocab, dim)
    node = "emb"
    for i in range(nlayer):
        blk, node = _transformer_block(
            "blk%d" % i, node, dim, nhead, ffn_mult * dim,
            attn_keys="causal = 1\n" + attn_extra)
        txt += "\n" + blk
    txt += """
layer[%s->logits] = conv:head
  kernel_size = 1
  nchannel = %d
  init_sigma = 0.05
layer[+0] = softmax
  seq = 1
netconfig = end
""" % (node, vocab)
    return txt


def smallthinker_netconfig(vocab: int = 151936, dim: int = 2560,
                           nhead: int = 28, nkvhead: int = 4,
                           head_dim: int = 128, nlayer: int = 52,
                           n_expert: int = 64, top_k: int = 6,
                           expert_width: int = 768, n_held: int = 0,
                           expert_offset: int = 0, window: int = 4096,
                           period=(0, 1, 1, 1), rope_theta: float = 1.5e6,
                           eps: float = 1e-6, remat: str = "moe") -> str:
    """SmallThinker-21BA3B (PowerInfer, 2025; the defaults are its
    published config.json) from the netconfig DSL: embed -> nlayer x [
    rmsnorm, attention (grouped-query, a head size of its own; layer l is
    global with no position signal where ``period[l % len] == 0`` and
    rotary inside a causal window of ``window`` keys where it is 1) +
    residual, rmsnorm, sparse ReGLU experts top_k of n_expert whose router
    reads the block's input + residual ] -> rmsnorm -> untied vocab head
    -> per-position softmax. No bias anywhere. Matrices start at
    normal(0, 0.02) and the embedding at normal(0, 1): the stream the
    routers read (un-normed) is then each token's own vector and not what
    attention averages into every position, so random weights route
    token by token as a trained router does.

    ``n_held`` / ``expert_offset`` / ``vocab`` / ``nlayer`` cut one chip's
    share of an expert-parallel deployment (the experts and the vocabulary
    rows this chip holds, the layers of this pipeline stage); the router
    keeps its ``n_expert`` outputs. ``remat`` names the layer kinds whose
    activations are recomputed in the backward pass ("moe", "attention",
    both with a blank between, or "")."""
    blocks = []
    for i in range(nlayer):
        local = period[i % len(period)]
        attn = ("nkvhead = %d\nhead_dim = %d\ncausal = 1\nrope = %d\n"
                "rope_base = %.10g\nattn_window = %d\nremat = %d\n"
                % (nkvhead, head_dim, local, rope_theta,
                   window if local else 0, "attention" in remat))
        blocks.append(dict(ffn=expert_width, attn_keys=attn, ffn_kind="moe",
                           ffn_keys=_moe_keys(n_expert, top_k, n_held,
                                              expert_offset, "reglu",
                                              remat)))
    return _decoder_lm(vocab, dim, nhead, blocks, eps)


SMALLTHINKER_ADAMW = ("updater = adamw\neta = 0.0000003\nbeta1 = 0.9\n"
                      "beta2 = 0.95\nadam_eps = 1e-08\nwd = 0.1\n"
                      "gain:wd = 0.0\n")


def _next_token(seq: int) -> str:
    return "label_vec[0,%d) = label\n" % seq


def smallthinker_conf(seq: int = 8192, batch_size: int = 1,
                      dev: str = "tpu", extra_cfg: str = "", **kw) -> str:
    """The whole training conf of the SmallThinker recipe: the netconfig,
    the shapes, and AdamW as large mixture-of-experts models are trained
    (beta 0.9 / 0.95, decay 0.1 on the matrices and none on the norms'
    gains) at eta 3e-7, a thousandth of a 3e-4 peak: the first steps of a
    linear warm-up, held constant. All assumed, the model's card gives
    none. (At 3e-4 from the first step the loss rises from 10.9 to 14.7
    and every router collapses onto one expert within three steps; at
    3e-6 the routers' loads still drift by a fifth within 45 steps; at
    3e-7 the bfloat16 copies of the weights hardly change and the routing
    is nearly at rest: PERF.md section 6, PR 29.)"""
    return _lm_conf(smallthinker_netconfig(**kw), SMALLTHINKER_ADAMW, seq,
                    batch_size, dev, extra_cfg, _next_token(seq))


def sdar_moe_netconfig(vocab: int = 151936, dim: int = 2048,
                       nhead: int = 32, nkvhead: int = 4,
                       head_dim: int = 128, nlayer: int = 48,
                       n_expert: int = 128, top_k: int = 8,
                       expert_width: int = 768, n_held: int = 0,
                       expert_offset: int = 0, seq: int = 8192,
                       block_len: int = 4, rope_theta: float = 1e6,
                       eps: float = 1e-6, remat: str = "moe") -> str:
    """SDAR-30B-A3B (JetLM, 2025; the defaults are its published
    config.json, ``model_type: sdar_moe``) as it is TRAINED, by diffusion
    over blocks, from the netconfig DSL. A sequence of ``seq`` tokens runs
    as 2 ``seq`` rows, [noised copy | clean copy]: embed -> nlayer x [
    rmsnorm, attention (grouped-query, a head size of its own, an rmsnorm
    over each head of q and k, rotary at the row's position in its copy,
    the block-diffusion mask of ``block_len``) + residual, rmsnorm, sparse
    SwiGLU experts top_k of n_expert whose router reads the normed stream
    + residual ] -> rmsnorm of the noised copy's rows alone -> untied
    vocab head -> per-position softmax weighted by the label field
    ``loss_weight`` (0 on positions left unmasked, 1/t on masked ones;
    ``io.blockdiff.noise_batch`` makes the rows and both fields). No bias
    anywhere; matrices start at normal(0, 0.02) and the embedding at
    normal(0, 1), for ``smallthinker_netconfig``'s reason: the stream the
    routers read (normed, after the attention's residual) is then each
    token's own vector and not what attention averages into a position.

    ``n_held`` / ``expert_offset`` / ``vocab`` / ``nlayer`` cut one chip's
    share of an expert-parallel deployment as ``smallthinker_netconfig``'s
    do; ``remat`` names the layer kinds recomputed in the backward pass."""
    attn = ("nkvhead = %d\nhead_dim = %d\ncausal = 0\n"
            "attn_mask = blockdiff\nblock_len = %d\nqk_norm = 1\n"
            "rope = 1\nrope_base = %.10g\nremat = %d\n"
            % (nkvhead, head_dim, block_len, rope_theta,
               "attention" in remat))
    blk = dict(ffn=expert_width, attn_keys=attn, ffn_kind="moe",
               ffn_keys=_moe_keys(n_expert, top_k, n_held, expert_offset,
                                  "swiglu", remat),
               router_on_input=False)
    return _decoder_lm(vocab, dim, nhead, [blk] * nlayer, eps,
                       norm_f_keys="  seq_rows = %d\n" % seq,
                       loss_keys="  weight_target = loss_weight\n")


SDAR_MOE_ADAMW = (SMALLTHINKER_ADAMW
                  + "qnorm:wd = 0.0\nknorm:wd = 0.0\n")


def sdar_moe_conf(seq: int = 8192, batch_size: int = 1, dev: str = "tpu",
                  extra_cfg: str = "", **kw) -> str:
    """The whole training conf of the block-diffusion recipe: the
    netconfig at ``seq`` tokens (2 ``seq`` rows), the shapes, the two
    label fields, and ``smallthinker_conf``'s AdamW (assumed there as
    here; the gains of the heads' norms take no decay either)."""
    return _lm_conf(sdar_moe_netconfig(seq=seq, **kw), SDAR_MOE_ADAMW,
                    2 * seq, batch_size, dev, extra_cfg,
                    _next_token(seq) + "label_vec[%d,%d) = loss_weight\n"
                    % (seq, 2 * seq))


def keye_dsa_netconfig(vocab: int = 151936, dim: int = 2048,
                       nhead: int = 32, nkvhead: int = 4,
                       head_dim: int = 128, nlayer: int = 48,
                       n_expert: int = 128, top_k: int = 8,
                       expert_width: int = 768, n_held: int = 0,
                       expert_offset: int = 0, index_heads: int = 16,
                       index_dim: int = 64, index_topk: int = 2048,
                       rope_theta: float = 1e7, eps: float = 1e-6,
                       remat: str = "moe") -> str:
    """The language model of Keye-VL-2.0-30B-A3B (Kwai-Keye, 2025; the
    defaults are the language model's keys of its published config.json,
    ``model_type: KeyeVL2``) trained next-token on text, from the
    netconfig DSL: embed -> nlayer x [ rmsnorm, attention (grouped-query,
    a head size of its own, an rmsnorm over each head of q and k, rotary,
    and ``attn_mask = dsa``: an indexer of ``index_heads`` heads of
    ``index_dim`` on one key head reads the normed stream detached, each
    query attends to the ``index_topk`` keys it ranks highest, and the
    indexer learns from the attention it selected for, a term the layer
    adds to the step's loss) + residual, rmsnorm, sparse SwiGLU experts
    top_k of n_expert whose router reads the normed stream + residual ]
    -> rmsnorm -> untied vocab head -> per-position softmax. The widths
    and the block are ``sdar_moe_netconfig``'s (the same Qwen3-MoE
    family); the mask and the indexer are this model's. The vision tower
    is left out: text tokens only, the three position streams of
    ``mrope_section`` are then one and the rotation is ``rope = 1``'s.

    ``n_held`` / ``expert_offset`` / ``vocab`` / ``nlayer`` cut one chip's
    share of an expert-parallel deployment as ``smallthinker_netconfig``'s
    do; ``remat`` names the layer kinds recomputed in the backward pass."""
    attn = ("nkvhead = %d\nhead_dim = %d\ncausal = 1\n"
            "attn_mask = dsa\nindex_heads = %d\nindex_dim = %d\n"
            "index_topk = %d\nqk_norm = 1\n"
            "rope = 1\nrope_base = %.10g\nremat = %d\n"
            % (nkvhead, head_dim, index_heads, index_dim, index_topk,
               rope_theta, "attention" in remat))
    blk = dict(ffn=expert_width, attn_keys=attn, ffn_kind="moe",
               ffn_keys=_moe_keys(n_expert, top_k, n_held, expert_offset,
                                  "swiglu", remat),
               router_on_input=False)
    return _decoder_lm(vocab, dim, nhead, [blk] * nlayer, eps)


KEYE_DSA_ADAMW = (SDAR_MOE_ADAMW
                  + "idx_gain:wd = 0.0\nidx_bias:wd = 0.0\n")


def keye_dsa_conf(seq: int = 8192, batch_size: int = 1, dev: str = "tpu",
                  extra_cfg: str = "", **kw) -> str:
    """The whole training conf of the sparse-attention recipe: the
    netconfig, the shapes, the next-token labels, and
    ``smallthinker_conf``'s AdamW (assumed there as here; the gains of
    the heads' norms and the indexer's LayerNorm take no decay)."""
    return _lm_conf(keye_dsa_netconfig(**kw), KEYE_DSA_ADAMW, seq,
                    batch_size, dev, extra_cfg, _next_token(seq))


# LFM2-24B-A2B's ``layer_types`` as published: a period of an attention
# layer and three conv layers, after two conv layers
LFM2_LAYER_TYPES = ("conv", "conv") + (
    "full_attention", "conv", "conv", "conv") * 9 + ("full_attention",
                                                     "conv")


def lfm2_moe_netconfig(vocab: int = 65536, dim: int = 2048,
                       nhead: int = 32, nkvhead: int = 8,
                       head_dim: int = 64,
                       layer_types=LFM2_LAYER_TYPES, n_dense: int = 2,
                       dense_width: int = 11776, n_expert: int = 64,
                       top_k: int = 4, expert_width: int = 1536,
                       n_held: int = 0, expert_offset: int = 0,
                       conv_kernel: int = 3, rope_theta: float = 1e6,
                       eps: float = 1e-5, remat: str = "moe ffn") -> str:
    """LFM2-24B-A2B (LiquidAI; the defaults are its published
    config.json, ``model_type: lfm2_moe``) trained next-token, from the
    netconfig DSL: embed -> one block a ``layer_types`` entry [ rmsnorm,
    a mixer + residual, rmsnorm, an FFN + residual ] -> rmsnorm
    (``embedding_norm``) -> untied vocab head -> per-position softmax.
    The mixer is the layer's kind: ``conv`` a gated short causal conv of
    ``conv_kernel`` taps (``shortconv``), ``full_attention`` grouped-query
    attention with an rmsnorm over each head of q and k (eps ``eps``) and
    rotary positions at ``rope_theta``. The first ``n_dense`` blocks'
    FFN is a dense SwiGLU of ``dense_width`` (``ffn``), the others' a
    ``moe`` layer: sparse SwiGLU experts top_k of n_expert whose router
    reads the normed stream, sigmoid scores, the top k of score + the
    fixed ``expert_bias`` chosen and weighted by their scores over their
    sum (``routed_scaling_factor`` is 1: no scale is written). No bias
    anywhere; matrices start at normal(0, 0.02) and the embedding at
    normal(0, 1), for
    ``smallthinker_netconfig``'s reason.

    ``n_held`` / ``expert_offset`` / ``vocab`` / ``layer_types`` /
    ``n_dense`` cut one chip's share of an expert-parallel deployment as
    ``smallthinker_netconfig``'s do; ``remat`` names the layer kinds whose
    activations are made again in the backward pass (``moe``, ``ffn``,
    ``attention``, blank-separated)."""
    kinds = remat.split()
    attn = ("nkvhead = %d\nhead_dim = %d\ncausal = 1\nqk_norm = 1\n"
            "qk_norm_eps = %.10g\nrope = 1\nrope_base = %.10g\n"
            "remat = %d\n" % (nkvhead, head_dim, eps, rope_theta,
                               "attention" in kinds))
    conv = "kernel = %d\n" % conv_kernel
    moe = _moe_keys(n_expert, top_k, n_held, expert_offset, "swiglu", remat,
                    "score = sigmoid\nexpert_bias = 1\n")
    dense = "remat = %d\n" % ("ffn" in kinds)
    blocks = []
    for i, kind in enumerate(layer_types):
        mixer = {"conv": ("shortconv", conv),
                 "full_attention": ("attention", attn)}[kind]
        ffn = (dict(ffn=dense_width, ffn_kind="ffn", ffn_keys=dense)
               if i < n_dense else
               dict(ffn=expert_width, ffn_kind="moe", ffn_keys=moe,
                    router_on_input=False))
        blocks.append(dict(ffn, mixer=mixer[0], attn_keys=mixer[1]))
    return _decoder_lm(vocab, dim, nhead, blocks, eps)


LFM2_ADAMW = SDAR_MOE_ADAMW


def lfm2_moe_conf(seq: int = 8192, batch_size: int = 2, dev: str = "tpu",
                  extra_cfg: str = "", **kw) -> str:
    """The whole training conf of the LFM2 recipe: the netconfig, the
    shapes, the next-token labels, and ``smallthinker_conf``'s AdamW
    (assumed there as here; the norms' gains take no decay, the expert
    bias no step at all: it is a fixed leaf)."""
    return _lm_conf(lfm2_moe_netconfig(**kw), LFM2_ADAMW, seq, batch_size,
                    dev, extra_cfg, _next_token(seq))


# Laguna-XS.2's ``layer_types`` as published: a full-attention layer and
# three sliding-window layers, ten times
LAGUNA_LAYER_TYPES = ("full_attention", "sliding_attention",
                      "sliding_attention", "sliding_attention") * 10
# the full layers' rotation, ``rope_parameters.full_attention``: YaRN over
# half of each head (the window layers' is plain, over the whole head)
LAGUNA_FULL_ROPE = dict(theta=5e5, rope_dim=64, factor=64.0,
                        original_len=4096, beta_fast=64.0, beta_slow=1.0,
                        attention_factor=1.4158883083359672)


def laguna_moe_netconfig(vocab: int = 100352, dim: int = 2048,
                         full_heads: int = 48, window_heads: int = 64,
                         nkvhead: int = 8, head_dim: int = 128,
                         layer_types=LAGUNA_LAYER_TYPES, window: int = 512,
                         full_rope=None, window_theta: float = 1e4,
                         n_dense: int = 1,
                         dense_width: int = 8192, n_expert: int = 256,
                         top_k: int = 8, expert_width: int = 512,
                         shared_width: int = 512, routed_scale: float = 2.5,
                         n_held: int = 0, expert_offset: int = 0,
                         eps: float = 1e-6, remat: str = "moe ffn") -> str:
    """Laguna-XS.2 (poolside; the defaults are its published config.json,
    ``model_type: laguna``) trained next-token, from the netconfig DSL:
    embed -> one block a ``layer_types`` entry [ rmsnorm, attention +
    residual, rmsnorm, an FFN + residual ] -> rmsnorm -> untied vocab
    head -> per-position softmax. Attention is grouped-query on
    ``nkvhead`` heads of ``head_dim`` with each head's output times a
    sigmoid gate of the normed input (``head_gate``); its kind is the
    layer's: ``full_attention`` causal with ``full_heads`` query heads and
    YaRN rotation over the first ``rope_dim`` features of a head
    (``full_rope``: ``LAGUNA_FULL_ROPE``'s keys), ``sliding_attention``
    causal inside a window of ``window`` keys with ``window_heads`` query
    heads and plain rotation over the whole head at ``window_theta``. The
    first ``n_dense`` blocks' FFN is a dense SwiGLU of ``dense_width``
    (``ffn``), the others' a ``moe`` layer: SwiGLU experts top_k of
    n_expert whose router reads the normed stream, sigmoid scores, the
    top k weighted by their scores over their sum times ``routed_scale``,
    and one shared SwiGLU expert of ``shared_width`` over every row. No
    bias anywhere; matrices start at normal(0, 0.02) and the embedding at
    normal(0, 1), for ``smallthinker_netconfig``'s reason. The window
    blocks are named ``b<i>w`` (``b<i>w_att``), the full ones ``b<i>``.

    ``n_held`` / ``expert_offset`` / ``vocab`` / ``layer_types`` /
    ``n_dense`` cut one chip's share of an expert-parallel deployment as
    ``smallthinker_netconfig``'s do; ``remat`` names the layer kinds whose
    activations are made again in the backward pass (``moe``, ``ffn``,
    ``attention``, blank-separated)."""
    kinds = remat.split()
    full = dict(LAGUNA_FULL_ROPE, **(full_rope or {}))
    common = ("nkvhead = %d\nhead_dim = %d\ncausal = 1\nhead_gate = 1\n"
              "rope = 1\n" % (nkvhead, head_dim))
    attn = {"full_attention": (full_heads, "", common + (
                "rope_base = %.10g\nrope_dim = %d\nrope_type = yarn\n"
                "yarn_factor = %.10g\nyarn_original_len = %d\n"
                "yarn_beta_fast = %.10g\nyarn_beta_slow = %.10g\n"
                "yarn_attention_factor = %.17g\n"
                % (full["theta"], full["rope_dim"], full["factor"],
                   full["original_len"], full["beta_fast"],
                   full["beta_slow"], full["attention_factor"]))),
            "sliding_attention": (window_heads, "w", common + (
                "rope_base = %.10g\nattn_window = %d\n"
                % (window_theta, window)))}
    moe = _moe_keys(n_expert, top_k, n_held, expert_offset, "swiglu", remat,
                    "score = sigmoid\nrouted_scale = %.10g\n"
                    "nshared_hidden = %d\n" % (routed_scale, shared_width))
    dense = "remat = %d\n" % ("ffn" in kinds)
    blocks = []
    for i, kind in enumerate(layer_types):
        heads, tag, keys = attn[kind]
        ffn = (dict(ffn=dense_width, ffn_kind="ffn", ffn_keys=dense)
               if i < n_dense else
               dict(ffn=expert_width, ffn_kind="moe", ffn_keys=moe,
                    router_on_input=False))
        blocks.append(dict(ffn, nhead=heads, prefix="b%d%s" % (i, tag),
                           attn_keys=keys + "remat = %d\n"
                           % ("attention" in kinds)))
    return _decoder_lm(vocab, dim, full_heads, blocks, eps)


LAGUNA_ADAMW = SDAR_MOE_ADAMW


def laguna_moe_conf(seq: int = 8192, batch_size: int = 1, dev: str = "tpu",
                    extra_cfg: str = "", **kw) -> str:
    """The whole training conf of the Laguna recipe: the netconfig, the
    shapes, the next-token labels, and ``smallthinker_conf``'s AdamW
    (assumed there as here; the norms' gains take no decay, every matrix,
    the heads' gates and the shared expert's among them, 0.1)."""
    return _lm_conf(laguna_moe_netconfig(**kw), LAGUNA_ADAMW, seq,
                    batch_size, dev, extra_cfg, _next_token(seq))


def transformer_lm_conf(vocab: int = 50, seq: int = 16,
                        batch_size: int = 8, dim: int = 64,
                        nhead: int = 4, nlayer: int = 2,
                        dev: str = "cpu", extra_cfg: str = "",
                        attn_extra: str = "") -> str:
    """The whole training conf of the LM recipe (netconfig + shapes +
    adam), as text — what a ``task = serve`` conf is appended to."""
    return (transformer_lm_netconfig(vocab, dim=dim, nhead=nhead,
                                     nlayer=nlayer,
                                     attn_extra=attn_extra) +
            "input_shape = 1,1,%d\n" % seq +
            "batch_size = %d\n" % batch_size +
            "label_vec[0,%d) = label\n" % seq +
            "updater = adam\neta = 0.003\n" +
            "dev = %s\n" % dev + extra_cfg)


def transformer_lm_trainer(**kw) -> Trainer:
    """Initialized trainer for ``transformer_lm_conf(**kw)``."""
    conf = transformer_lm_conf(**kw)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def vit_netconfig(n_class: int, image_hw: int = 32, patch: int = 4,
                  dim: int = 64, nhead: int = 4, nlayer: int = 2,
                  ffn_mult: int = 2) -> str:
    """Vision transformer from the netconfig DSL (beyond the reference —
    composes existing pieces): patch-embedding conv (kernel = stride =
    patch) -> im2seq -> n x [batch_norm, RoPE attention + residual,
    1x1-conv FFN + residual] -> mean-pool over positions -> fullc head.
    RoPE supplies the position signal (row-major patch order, the im2seq
    flattening), so no learned position table is needed."""
    check_msg = "vit: patch must divide image_hw"
    assert image_hw % patch == 0, check_msg
    npos = (image_hw // patch) ** 2
    txt = """
netconfig = start
layer[0->pe] = conv:patch_embed
  kernel_size = %d
  stride = %d
  nchannel = %d
  random_type = xavier
layer[pe->sq] = im2seq
""" % (patch, patch, dim)
    node = "sq"
    for i in range(nlayer):
        blk, node = _transformer_block(
            "vb%d" % i, node, dim, nhead, ffn_mult * dim,
            attn_keys="rope = 1\n", norm=True)
        txt += "\n" + blk
    txt += """
layer[%s->gp] = avg_pooling
  kernel_height = 1
  kernel_width = %d
  stride = %d
layer[gp->fl] = flatten
layer[fl->out] = fullc:head
  nhidden = %d
  random_type = xavier
layer[+0] = softmax
netconfig = end
""" % (node, npos, npos, n_class)
    return txt


def vit_trainer(n_class: int = 10, image_hw: int = 32, patch: int = 4,
                batch_size: int = 16, dim: int = 64, nhead: int = 4,
                nlayer: int = 2, ffn_mult: int = 2, dev: str = "cpu",
                extra_cfg: str = "") -> Trainer:
    """Vision-transformer trainer (shrink image_hw/dim/nlayer for tests)."""
    conf = (vit_netconfig(n_class, image_hw=image_hw, patch=patch,
                          dim=dim, nhead=nhead, nlayer=nlayer,
                          ffn_mult=ffn_mult) +
            "input_shape = 3,%d,%d\n" % (image_hw, image_hw) +
            "batch_size = %d\n" % batch_size +
            "updater = adamw\neta = 0.003\nwd = 0.01\n" +
            "dev = %s\n" % dev + extra_cfg)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


def _res_block(idx: int, node_in: str, nch: int, stride: int = 1,
               project: bool = False) -> Tuple[str, str]:
    """Basic residual block (two 3x3 convs + batch_norm, identity or
    1x1-projection shortcut, post-add relu), expressed in the layer DSL —
    beyond the reference's era (it ships concat but no residual nets); the
    `add` layer makes the family expressible."""
    p = "rb%d" % idx
    main_in = "%s_s0" % p
    short_in = "%s_s1" % p
    txt = "layer[%s->%s,%s] = split\n" % (node_in, main_in, short_in)
    txt += """layer[{mi}->{p}_c1] = conv:{p}_c1
  kernel_size = 3
  pad = 1
  stride = {stride}
  nchannel = {nch}
  random_type = kaiming
  no_bias = 1
layer[{p}_c1->{p}_b1] = batch_norm:{p}_b1
layer[{p}_b1->{p}_r1] = relu
layer[{p}_r1->{p}_c2] = conv:{p}_c2
  kernel_size = 3
  pad = 1
  nchannel = {nch}
  random_type = kaiming
  no_bias = 1
layer[{p}_c2->{p}_b2] = batch_norm:{p}_b2
""".format(p=p, mi=main_in, nch=nch, stride=stride)
    if project:
        txt += """layer[{si}->{p}_sc] = conv:{p}_sc
  kernel_size = 1
  stride = {stride}
  nchannel = {nch}
  random_type = kaiming
  no_bias = 1
layer[{p}_sc->{p}_sb] = batch_norm:{p}_sb
layer[{p}_b2,{p}_sb->{p}_add] = add
""".format(p=p, si=short_in, nch=nch, stride=stride)
    else:
        txt += "layer[%s_b2,%s->%s_add] = add\n" % (p, short_in, p)
    txt += "layer[%s_add->%s_out] = relu\n" % (p, p)
    return txt, "%s_out" % p


def resnet_netconfig(depths=(2, 2, 2, 2), base_ch: int = 64,
                     n_class: int = 1000, final_pool: int = 7) -> str:
    """ResNet-18-shaped netconfig (depths=(2,2,2,2)); shrink depths/base_ch
    for tests."""
    txt = "netconfig = start\n"
    txt += """layer[0->stem] = conv:stem
  kernel_size = 7
  pad = 3
  stride = 2
  nchannel = %d
  random_type = kaiming
  no_bias = 1
layer[stem->stem_b] = batch_norm:stem_b
layer[stem_b->stem_r] = relu
layer[stem_r->stem_p] = max_pooling
  kernel_size = 3
  stride = 2
""" % base_ch
    node = "stem_p"
    idx = 0
    for stage, n_blocks in enumerate(depths):
        nch = base_ch * (2 ** stage)
        for b in range(n_blocks):
            first = (b == 0 and stage > 0)
            blk, node = _res_block(idx, node, nch,
                                   stride=2 if first else 1,
                                   project=first)
            txt += blk
            idx += 1
    txt += """layer[%s->gap] = avg_pooling
  kernel_size = %d
  stride = %d
layer[gap->flat] = flatten
layer[flat->fc] = fullc:fc
  nhidden = %d
  random_type = kaiming
layer[fc->fc] = softmax
netconfig = end
""" % (node, final_pool, final_pool, n_class)
    return txt


def resnet_trainer(batch_size: int = 128, input_hw: int = 224,
                   dev: str = "tpu", n_class: int = 1000,
                   depths=(2, 2, 2, 2), base_ch: int = 64,
                   extra_cfg: str = "") -> Trainer:
    """ResNet-18-shaped trainer (shrink depths/base_ch/input_hw for
    tests)."""
    # stem(2) * pool(2) * one stride-2 per stage after the first
    downsample = 4 * (2 ** (len(depths) - 1))
    final_pool = max(input_hw // downsample, 1)
    conf = (resnet_netconfig(depths, base_ch, n_class,
                             final_pool=final_pool) +
            "input_shape = 3,%d,%d\n" % (input_hw, input_hw) +
            "batch_size = %d\n" % batch_size +
            "eta = 0.1\nmomentum = 0.9\nwd = 0.0001\n" +
            "dev = %s\n" % dev + extra_cfg)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


# VGG (Simonyan & Zisserman 2014) — contemporary of the reference's era;
# deep uniform 3x3 stacks, the natural customer of `remat = 1` (13 conv
# activations at 224x224 otherwise dominate HBM)
VGG_STAGES = {
    "vgg11": ((64,), (128,), (256, 256), (512, 512), (512, 512)),
    "vgg16": ((64, 64), (128, 128), (256, 256, 256),
              (512, 512, 512), (512, 512, 512)),
}


def vgg_netconfig(arch: str = "vgg16", n_class: int = 1000,
                  fc_dim: int = 4096, remat: int = 0,
                  dropout: float = 0.5) -> str:
    """VGG in the layer DSL: 5 stages of 3x3/pad-1 conv+relu stacks, each
    followed by a 2x2/stride-2 max pool, then fc-relu-dropout x2 and the
    classifier head."""
    txt = "netconfig=start\n"
    if remat:
        txt += "remat = 1\n"
    node = "0"
    for s, widths in enumerate(VGG_STAGES[arch]):
        for c, width in enumerate(widths):
            name = "conv%d_%d" % (s + 1, c + 1)
            txt += """layer[%s->%s] = conv:%s
  kernel_size = 3
  pad = 1
  nchannel = %d
layer[%s->%sr] = relu
""" % (node, name, name, width, name, name)
            node = name + "r"
        txt += """layer[%s->pool%d] = max_pooling
  kernel_size = 2
  stride = 2
""" % (node, s + 1)
        node = "pool%d" % (s + 1)
    txt += "layer[%s->fl] = flatten\n" % node
    node = "fl"
    for i in (6, 7):
        txt += """layer[%s->fc%d] = fullc:fc%d
  nhidden = %d
layer[fc%d->fc%dr] = relu
layer[fc%dr->fc%dr] = dropout
  threshold = %g
""" % (node, i, i, fc_dim, i, i, i, i, dropout)
        node = "fc%dr" % i
    txt += """layer[%s->out] = fullc:head
  nhidden = %d
layer[+0] = softmax
netconfig=end
random_type = kaiming
metric = error
""" % (node, n_class)
    return txt


def vgg_trainer(batch_size: int = 64, input_hw: int = 224,
                dev: str = "tpu", n_class: int = 1000,
                arch: str = "vgg16", fc_dim: int = 4096,
                remat: int = 0, dropout: float = 0.5,
                extra_cfg: str = "") -> Trainer:
    """VGG trainer with the paper recipe; shrink input_hw/fc_dim for
    tests (input must be a multiple of 32 to survive the 5 pools)."""
    assert input_hw % 32 == 0, "VGG needs input divisible by 32"
    conf = (vgg_netconfig(arch, n_class, fc_dim=fc_dim,
                      remat=remat, dropout=dropout) +
            "input_shape = 3,%d,%d\n" % (input_hw, input_hw) +
            "batch_size = %d\n" % batch_size +
            "eta = 0.01\nmomentum = 0.9\nwd = 0.0005\n" +
            "dev = %s\n" % dev + extra_cfg)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr


# MobileNet-V1-style depthwise-separable stack — the grouped-conv
# extreme (ngroup = C: one input channel per group), exercising the
# reference's in-layer model-splitting mechanism
# (src/layer/convolution_layer-inl.hpp:92-96) at its limit while being
# the canonical bandwidth-lean conv recipe for edge/serving. Beyond the
# reference's zoo (its era predates depthwise separability going
# mainstream); built entirely from the stock `conv` layer.

MOBILENET_BLOCKS = ((64, 1), (128, 2), (128, 1), (256, 2),
                    (256, 1), (512, 2), (512, 1))


def _mobilenet_final_pool(blocks, input_hw: int) -> int:
    """GAP kernel for the final feature map: input / (stem 2x * block
    strides) — ONE definition so netconfig and trainer can't drift."""
    downsample = 2
    for _, s in blocks:
        downsample *= s
    return max(input_hw // downsample, 1)


def mobilenet_netconfig(n_class: int = 1000, base_ch: int = 32,
                        blocks=MOBILENET_BLOCKS,
                        final_pool: int = 0) -> str:
    """(out_channels, stride) per depthwise-separable block; shrink
    ``blocks``/``base_ch`` for tests. final_pool 0 = global average
    pool for a 224 input (derived from the block strides)."""
    if not final_pool:
        final_pool = _mobilenet_final_pool(blocks, 224)
    txt = """netconfig = start
layer[0->stem] = conv:stem
  kernel_size = 3
  pad = 1
  stride = 2
  nchannel = %d
  random_type = kaiming
  no_bias = 1
layer[stem->stem_b] = batch_norm:stem_b
layer[stem_b->stem_r] = relu
""" % base_ch
    node, c = "stem_r", base_ch
    for i, (ch, stride) in enumerate(blocks):
        txt += """layer[%s->dw%d] = conv:dw%d
  kernel_size = 3
  pad = 1
  stride = %d
  nchannel = %d
  ngroup = %d
  random_type = kaiming
  no_bias = 1
layer[dw%d->dwb%d] = batch_norm:dwb%d
layer[dwb%d->dwr%d] = relu
layer[dwr%d->pw%d] = conv:pw%d
  kernel_size = 1
  nchannel = %d
  random_type = kaiming
  no_bias = 1
layer[pw%d->pwb%d] = batch_norm:pwb%d
layer[pwb%d->pwr%d] = relu
""" % (node, i, i, stride, c, c, i, i, i, i, i, i, i, i, ch,
            i, i, i, i, i)
        node, c = "pwr%d" % i, ch
    txt += """layer[%s->gap] = avg_pooling
  kernel_size = %d
  stride = %d
layer[gap->flat] = flatten
layer[flat->fc] = fullc:fc
  nhidden = %d
  random_type = kaiming
layer[fc->fc] = softmax
netconfig = end
""" % (node, final_pool, final_pool, n_class)
    return txt


def mobilenet_trainer(batch_size: int = 256, input_hw: int = 224,
                      dev: str = "tpu", n_class: int = 1000,
                      base_ch: int = 32,
                      blocks=MOBILENET_BLOCKS,
                      extra_cfg: str = "") -> Trainer:
    """Depthwise-separable trainer (shrink blocks/base_ch/input_hw for
    tests)."""
    final_pool = _mobilenet_final_pool(blocks, input_hw)
    conf = (mobilenet_netconfig(n_class, base_ch, blocks,
                                final_pool=final_pool) +
            "input_shape = 3,%d,%d\n" % (input_hw, input_hw) +
            "batch_size = %d\n" % batch_size +
            "eta = 0.1\nmomentum = 0.9\nwd = 0.0001\n" +
            "dev = %s\n" % dev + extra_cfg)
    tr = Trainer()
    for k, v in parse_config_string(conf):
        tr.set_param(k, v)
    tr.init_model()
    return tr
