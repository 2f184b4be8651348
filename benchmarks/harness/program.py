"""Builds the system under test from a configuration file: the zoo model
the file's ``builder`` names, as ``ZooModel.init()`` builds it, but
holding the benchmark's weights. Nothing here is specific to one model."""

from __future__ import annotations

from benchmarks.harness import loader


def merged(d: dict, rehearse: bool) -> dict:
    """A configuration's or a traffic file's parameters, with its
    ``rehearse`` overrides laid over them for a CPU rehearsal."""
    out = {k: v for k, v in d.items() if k != "rehearse"}
    if rehearse:
        for k, v in d.get("rehearse", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) else v
    return out


def build_net(config: dict, params: dict, serving_only: bool = False):
    """``config["builder"](**config["arguments"])``'s network, initialised
    with ``params`` (checked against the shapes the program's own layers
    declare). ``config["max_cache"]``, where given, is set on every layer
    that has one, as a serving launcher does. ``serving_only`` drops the
    updater state that ``init()`` allocates, which a server never reads."""
    import jax

    model = loader.resolve(config["builder"])(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in config["arguments"].items()})
    conf = model.conf()
    conf.compute_dtype = model.compute_dtype
    if type(conf).__name__ == "ComputationGraphConfiguration":
        from deeplearning4j_tpu.nn.graph import ComputationGraph as Net
    else:
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork as Net
    net = Net(conf)
    if "max_cache" in config:
        for v in conf.vertices.values():
            layer = getattr(v, "layer", None)
            if layer is not None and hasattr(layer, "max_cache"):
                layer.max_cache = int(config["max_cache"])
    want = jax.eval_shape(lambda: {
        n: conf.vertices[n].init_params(jax.random.PRNGKey(0), "float32")
        for n in conf.topo_order})
    tree = {n: params.get(n, {}) for n in conf.topo_order}
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    if shapes(tree) != shapes(want):
        raise ValueError("the benchmark's weights do not have the shapes "
                         "the program's network declares")
    net.init(params=tree)
    if serving_only:
        net.updater_state = None
    return net
