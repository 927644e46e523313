"""ResNet v2 (pre-activation) symbol factory.

Reference: ``example/image-classification/symbols/resnet.py`` (He et al.,
"Identity Mappings in Deep Residual Networks").  Built with the mx.sym API;
on TPU every conv lowers to one lax.conv_general_dilated on the MXU and BN +
ReLU fuse into the surrounding HLO.
"""
from __future__ import annotations

from .. import symbol as sym
from ..telemetry.spans import span


def residual_unit(data, num_filter, stride, dim_match, name,
                  bottle_neck=True, bn_mom=0.9, workspace=256):
    """One pre-activation residual unit."""
    if bottle_neck:
        bn1 = sym.BatchNorm(data=data, fix_gamma=False, eps=2e-5,
                            momentum=bn_mom, name=name + "_bn1")
        act1 = sym.Activation(data=bn1, act_type="relu",
                              name=name + "_relu1")
        conv1 = sym.Convolution(data=act1, num_filter=int(num_filter * 0.25),
                                kernel=(1, 1), stride=(1, 1), pad=(0, 0),
                                no_bias=True, workspace=workspace,
                                name=name + "_conv1")
        bn2 = sym.BatchNorm(data=conv1, fix_gamma=False, eps=2e-5,
                            momentum=bn_mom, name=name + "_bn2")
        act2 = sym.Activation(data=bn2, act_type="relu",
                              name=name + "_relu2")
        conv2 = sym.Convolution(data=act2, num_filter=int(num_filter * 0.25),
                                kernel=(3, 3), stride=stride, pad=(1, 1),
                                no_bias=True, workspace=workspace,
                                name=name + "_conv2")
        bn3 = sym.BatchNorm(data=conv2, fix_gamma=False, eps=2e-5,
                            momentum=bn_mom, name=name + "_bn3")
        act3 = sym.Activation(data=bn3, act_type="relu",
                              name=name + "_relu3")
        conv3 = sym.Convolution(data=act3, num_filter=num_filter,
                                kernel=(1, 1), stride=(1, 1), pad=(0, 0),
                                no_bias=True, workspace=workspace,
                                name=name + "_conv3")
        if dim_match:
            shortcut = data
        else:
            shortcut = sym.Convolution(data=act1, num_filter=num_filter,
                                       kernel=(1, 1), stride=stride,
                                       no_bias=True, workspace=workspace,
                                       name=name + "_sc")
        return conv3 + shortcut
    bn1 = sym.BatchNorm(data=data, fix_gamma=False, momentum=bn_mom,
                        eps=2e-5, name=name + "_bn1")
    act1 = sym.Activation(data=bn1, act_type="relu", name=name + "_relu1")
    conv1 = sym.Convolution(data=act1, num_filter=num_filter, kernel=(3, 3),
                            stride=stride, pad=(1, 1), no_bias=True,
                            workspace=workspace, name=name + "_conv1")
    bn2 = sym.BatchNorm(data=conv1, fix_gamma=False, momentum=bn_mom,
                        eps=2e-5, name=name + "_bn2")
    act2 = sym.Activation(data=bn2, act_type="relu", name=name + "_relu2")
    conv2 = sym.Convolution(data=act2, num_filter=num_filter, kernel=(3, 3),
                            stride=(1, 1), pad=(1, 1), no_bias=True,
                            workspace=workspace, name=name + "_conv2")
    if dim_match:
        shortcut = data
    else:
        shortcut = sym.Convolution(data=act1, num_filter=num_filter,
                                   kernel=(1, 1), stride=stride,
                                   no_bias=True, workspace=workspace,
                                   name=name + "_sc")
    return conv2 + shortcut


def residual_unit_v1(data, num_filter, stride, dim_match, name,
                     bottle_neck=True, bn_mom=0.9, workspace=256):
    """Classic post-activation unit (reference
    symbols/resnet-v1-fp16.py): conv-bn-relu chains, bn on the
    projection shortcut, relu after the residual add."""
    if bottle_neck:
        conv1 = sym.Convolution(data=data, num_filter=int(num_filter * 0.25),
                                kernel=(1, 1), stride=stride, pad=(0, 0),
                                no_bias=True, workspace=workspace,
                                name=name + "_conv1")
        bn1 = sym.BatchNorm(data=conv1, fix_gamma=False, eps=2e-5,
                            momentum=bn_mom, name=name + "_bn1")
        act1 = sym.Activation(data=bn1, act_type="relu",
                              name=name + "_relu1")
        conv2 = sym.Convolution(data=act1, num_filter=int(num_filter * 0.25),
                                kernel=(3, 3), stride=(1, 1), pad=(1, 1),
                                no_bias=True, workspace=workspace,
                                name=name + "_conv2")
        bn2 = sym.BatchNorm(data=conv2, fix_gamma=False, eps=2e-5,
                            momentum=bn_mom, name=name + "_bn2")
        act2 = sym.Activation(data=bn2, act_type="relu",
                              name=name + "_relu2")
        conv3 = sym.Convolution(data=act2, num_filter=num_filter,
                                kernel=(1, 1), stride=(1, 1), pad=(0, 0),
                                no_bias=True, workspace=workspace,
                                name=name + "_conv3")
        bn3 = sym.BatchNorm(data=conv3, fix_gamma=False, eps=2e-5,
                            momentum=bn_mom, name=name + "_bn3")
        if dim_match:
            shortcut = data
        else:
            sc = sym.Convolution(data=data, num_filter=num_filter,
                                 kernel=(1, 1), stride=stride, no_bias=True,
                                 workspace=workspace, name=name + "_sc")
            shortcut = sym.BatchNorm(data=sc, fix_gamma=False, eps=2e-5,
                                     momentum=bn_mom, name=name + "_sc_bn")
        return sym.Activation(data=bn3 + shortcut, act_type="relu",
                              name=name + "_relu")
    conv1 = sym.Convolution(data=data, num_filter=num_filter, kernel=(3, 3),
                            stride=stride, pad=(1, 1), no_bias=True,
                            workspace=workspace, name=name + "_conv1")
    bn1 = sym.BatchNorm(data=conv1, fix_gamma=False, eps=2e-5,
                        momentum=bn_mom, name=name + "_bn1")
    act1 = sym.Activation(data=bn1, act_type="relu", name=name + "_relu1")
    conv2 = sym.Convolution(data=act1, num_filter=num_filter, kernel=(3, 3),
                            stride=(1, 1), pad=(1, 1), no_bias=True,
                            workspace=workspace, name=name + "_conv2")
    bn2 = sym.BatchNorm(data=conv2, fix_gamma=False, eps=2e-5,
                        momentum=bn_mom, name=name + "_bn2")
    if dim_match:
        shortcut = data
    else:
        sc = sym.Convolution(data=data, num_filter=num_filter, kernel=(1, 1),
                             stride=stride, no_bias=True,
                             workspace=workspace, name=name + "_sc")
        shortcut = sym.BatchNorm(data=sc, fix_gamma=False, eps=2e-5,
                                 momentum=bn_mom, name=name + "_sc_bn")
    return sym.Activation(data=bn2 + shortcut, act_type="relu",
                          name=name + "_relu")


def resnet(units, num_stages, filter_list, num_classes, image_shape,
           bottle_neck=True, bn_mom=0.9, workspace=256, version=2):
    """Build the full network (reference resnet.py resnet())."""
    with span("model.build", category="model", model="resnet"):
        return _resnet(units, num_stages, filter_list, num_classes,
                       image_shape, bottle_neck, bn_mom, workspace, version)


def _resnet(units, num_stages, filter_list, num_classes, image_shape,
            bottle_neck, bn_mom, workspace, version):
    num_unit = len(units)
    assert num_unit == num_stages
    data = sym.Variable(name="data")
    data = sym.identity(data, name="id")
    (nchannel, height, width) = image_shape
    if version == 2:
        # the pre-activation net normalizes raw input (v1 does not)
        data = sym.BatchNorm(data=data, fix_gamma=True, eps=2e-5,
                             momentum=bn_mom, name="bn_data")
    if height <= 32:  # cifar
        body = sym.Convolution(data=data, num_filter=filter_list[0],
                               kernel=(3, 3), stride=(1, 1), pad=(1, 1),
                               no_bias=True, name="conv0",
                               workspace=workspace)
        if version == 1:
            body = sym.BatchNorm(data=body, fix_gamma=False, eps=2e-5,
                                 momentum=bn_mom, name="bn0")
            body = sym.Activation(data=body, act_type="relu", name="relu0")
    else:  # imagenet
        body = sym.Convolution(data=data, num_filter=filter_list[0],
                               kernel=(7, 7), stride=(2, 2), pad=(3, 3),
                               no_bias=True, name="conv0",
                               workspace=workspace)
        body = sym.BatchNorm(data=body, fix_gamma=False, eps=2e-5,
                             momentum=bn_mom, name="bn0")
        body = sym.Activation(data=body, act_type="relu", name="relu0")
        body = sym.Pooling(data=body, kernel=(3, 3), stride=(2, 2),
                           pad=(1, 1), pool_type="max", name="pool0")

    unit = residual_unit if version == 2 else residual_unit_v1
    for i in range(num_stages):
        body = unit(
            body, filter_list[i + 1],
            (1 if i == 0 else 2, 1 if i == 0 else 2), False,
            name="stage%d_unit%d" % (i + 1, 1), bottle_neck=bottle_neck,
            workspace=workspace, bn_mom=bn_mom)
        for j in range(units[i] - 1):
            body = unit(body, filter_list[i + 1], (1, 1), True,
                                 name="stage%d_unit%d" % (i + 1, j + 2),
                                 bottle_neck=bottle_neck,
                                 workspace=workspace, bn_mom=bn_mom)
    if version == 2:
        # pre-activation nets need one final BN+relu before pooling
        body = sym.BatchNorm(data=body, fix_gamma=False, eps=2e-5,
                             momentum=bn_mom, name="bn1")
        body = sym.Activation(data=body, act_type="relu", name="relu1")
    pool1 = sym.Pooling(data=body, global_pool=True, kernel=(7, 7),
                        pool_type="avg", name="pool1")
    flat = sym.Flatten(data=pool1)
    fc1 = sym.FullyConnected(data=flat, num_hidden=num_classes, name="fc1")
    return sym.SoftmaxOutput(data=fc1, name="softmax")


def get_symbol(num_classes=1000, num_layers=50, image_shape="3,224,224",
               conv_workspace=256, version=2, **kwargs):
    """Symbol factory keyed by depth (reference resnet.py get_symbol);
    version=1 builds post-activation units (reference resnet-v1-fp16.py
    architecture, in f32/bf16 — dtype comes from the trainer)."""
    image_shape = [int(x) for x in image_shape.split(",")] \
        if isinstance(image_shape, str) else list(image_shape)
    (nchannel, height, width) = image_shape
    if height <= 28:
        num_stages = 3
        if (num_layers - 2) % 9 == 0 and num_layers >= 164:
            per_unit = [(num_layers - 2) // 9]
            filter_list = [16, 64, 128, 256]
            bottle_neck = True
        elif (num_layers - 2) % 6 == 0 and num_layers < 164:
            per_unit = [(num_layers - 2) // 6]
            filter_list = [16, 16, 32, 64]
            bottle_neck = False
        else:
            raise ValueError("no experiments done on num_layers %d"
                             % num_layers)
        units = per_unit * num_stages
    else:
        if num_layers >= 50:
            filter_list = [64, 256, 512, 1024, 2048]
            bottle_neck = True
        else:
            filter_list = [64, 64, 128, 256, 512]
            bottle_neck = False
        num_stages = 4
        units = {
            18: [2, 2, 2, 2],
            34: [3, 4, 6, 3],
            50: [3, 4, 6, 3],
            101: [3, 4, 23, 3],
            152: [3, 8, 36, 3],
            200: [3, 24, 36, 3],
            269: [3, 30, 48, 8],
        }.get(num_layers)
        if units is None:
            raise ValueError("no experiments done on num_layers %d"
                             % num_layers)

    return resnet(units=units, num_stages=num_stages,
                  filter_list=filter_list, num_classes=num_classes,
                  image_shape=image_shape, version=version,
                  bottle_neck=bottle_neck,
                  workspace=conv_workspace)
